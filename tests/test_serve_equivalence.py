"""End-to-end serving correctness: decoding through the SpeedMalloc paged KV
engine must reproduce the full-sequence forward logits (teacher-forced),
for every architecture family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.freelist import validate_freelist
from repro.models import init_params, make_paged_config
from repro.models.transformer import forward
from repro.serve.engine import ServingEngine

FAMILY_REPS = [
    "deepseek-7b",        # dense MHA
    "gemma3-1b",          # local:global + tied embeddings
    "mixtral-8x7b",       # MoE + SWA
    "phi-3-vision-4.2b",  # vlm prefix
    "rwkv6-7b",           # attention-free
    "zamba2-1.2b",        # hybrid mamba2 + shared attn
    "whisper-medium",     # enc-dec + cross attention
]


@pytest.mark.parametrize("arch", FAMILY_REPS)
def test_decode_matches_forward(arch, rng):
    n_prefill, n_decode = 7, 4
    cfg = smoke_config(arch)
    params = init_params(cfg, dtype=jnp.float32)
    toks = rng.randint(0, cfg.vocab_size, size=(n_prefill + n_decode,)).astype(np.int32)
    kvcfg = make_paged_config(cfg, seq_len=64, lanes=2, page_size=4,
                              dtype=jnp.float32)
    eng = ServingEngine(cfg, kvcfg, params, dtype=jnp.float32)

    frames = patches = None
    fkw = {}
    if cfg.family == "audio":
        frames = rng.randn(cfg.encoder_seq_len, cfg.d_model).astype(np.float32)
        fkw["encoder_frames"] = jnp.asarray(frames)[None]
    if cfg.family == "vlm":
        patches = rng.randn(4, cfg.d_model).astype(np.float32)
        fkw["prefix_embeds"] = jnp.asarray(patches)[None]

    eng.admit(0, toks[:n_prefill], frames=frames, patches=patches)
    validate_freelist(eng.state.paged.alloc)

    errs = []
    for t in range(n_decode):
        eng.state = eng.state._replace(
            tokens=eng.state.tokens.at[0].set(int(toks[n_prefill + t])))
        # the jitted step, then its K/V written into the pool in place
        logits, _ = eng.decode_step()
        ref = forward(params, cfg, jnp.asarray(toks[:n_prefill + t + 1])[None],
                      remat=False, **fkw)
        ref_last = np.asarray(ref[0, -1])
        got = np.asarray(logits[0])
        errs.append(np.max(np.abs(got - ref_last))
                    / (np.max(np.abs(ref_last)) + 1e-9))
    validate_freelist(eng.state.paged.alloc)
    assert max(errs) < 2e-3, (arch, errs)

"""Serve deepseek-7b at its published widths on one TPU chip, end to end.

    python3 chip_smoke.py

One process, one chip.  The run:

1. reads ``jax.devices()`` and exits non-zero unless the platform is
   ``tpu`` (there is no CPU fallback: CPU rehearsal is the tests' job);
2. builds deepseek-7b from its registered config at published widths
   (d_model 4096, 32 heads, 32 kv heads, head_dim 128, d_ff 11008, vocab
   102400) with bf16 weights from a seed, its depth cut to ``LAYERS`` so
   the weights plus two copies of the KV pool fit 16 GB of HBM;
3. serves ``REQUESTS`` seeded requests on ``LANES`` lanes for
   ``NEW_TOKENS`` new tokens each, through ``ServingEngine`` +
   ``Scheduler`` + ``serve_loop`` exactly as ``repro.launch.serve`` builds
   them, once with the ``jnp`` allocator backend and once with the compiled
   fused support-core kernel (``kernel``);
4. requires every request served with no allocator failure, the paged-KV
   invariants holding after each run, and tokens plus the final
   ``FreeListState`` bit-identical between the two backends.

The last line of standard output is one JSON object naming the device;
every earlier line is a note, not a metric.  Any failed phase raises, so
the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.base import get_config  # noqa: E402
from repro.core.paged_kv import validate_paged_kv  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import serve_loop, synth_requests  # noqa: E402
from repro.models import init_params, make_paged_config  # noqa: E402
from repro.serve.engine import ServingEngine  # noqa: E402
from repro.serve.scheduler import Scheduler, make_scheduler_config  # noqa: E402

LAYERS = 16          # of deepseek-7b's 30: ~8.2 GB of bf16 weights
LANES = 4
REQUESTS = 6
NEW_TOKENS = 16
PAGE_SIZE = 16
SEQ_LEN = 256        # as repro.launch.serve sizes the pool
SEED = 0


def check_device():
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX reports platform "
                 f"{dev.platform!r}); this run needs the chip")
    return dev, len(devices)


def serve_once(cfg, kvcfg, params, backend: str, dev) -> dict:
    """One closed-loop serve run on a fresh engine; returns its outcome."""
    scfg = make_scheduler_config(cfg, kvcfg, max_prompt_len=128)
    requests = synth_requests(cfg, REQUESTS, np.random.RandomState(SEED))
    eng = ServingEngine(cfg, kvcfg, params, dtype=jnp.bfloat16,
                        sched_cfg=scfg, alloc_backend=backend)
    sched = Scheduler(scfg)
    t0 = time.perf_counter()
    steps = serve_loop(eng, sched, requests, NEW_TOKENS, verbose=False)
    wall_s = time.perf_counter() - t0

    served = len(sched.finished)
    if served != REQUESTS or sched.failed or sched.waiting \
            or eng.stats.alloc_failures:
        raise RuntimeError(
            f"{backend}: served {served}/{REQUESTS}, failed="
            f"{len(sched.failed)} stranded={len(sched.waiting)} "
            f"alloc_failures={eng.stats.alloc_failures}")
    validate_paged_kv(kvcfg, eng.state.paged, tenants=eng.tenants)
    tokens = {r.rid: list(r.output) for r in sched.finished}
    if any(len(t) != NEW_TOKENS for t in tokens.values()):
        raise RuntimeError(f"{backend}: a request stopped short of "
                           f"{NEW_TOKENS} tokens: {tokens}")
    alloc = {f: np.asarray(getattr(eng.state.paged.alloc, f))
             for f in eng.state.paged.alloc._fields}
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"{backend}: served {served}/{REQUESTS} requests in {steps} decode "
          f"steps; invariants I1-I6 hold; decode compile "
          f"{eng.stats.decode_compile_us / 1e6:.3f} s "
          f"({eng.stats.decode_compiles} executable), prefill compiles "
          f"{eng.stats.prefill_compiles}; serve wall {wall_s:.3f} s; "
          f"peak_bytes_in_use {peak}", flush=True)
    return {"tokens": tokens, "alloc": alloc}


def main() -> None:
    cache_dir = enable_compile_cache()
    dev, count = check_device()
    print(f"compile cache: {cache_dir}", flush=True)

    full = get_config("deepseek-7b")
    cfg = dataclasses.replace(full, num_layers=LAYERS)
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads} "
          f"kv_heads={cfg.num_kv_heads} head_dim={cfg.resolved_head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} bf16; depth cut "
          f"{full.num_layers} -> {cfg.num_layers} layers", flush=True)
    kvcfg = make_paged_config(cfg, seq_len=SEQ_LEN, lanes=LANES,
                              page_size=PAGE_SIZE, dtype=jnp.bfloat16)
    pool_bytes = 2 * kvcfg.num_pages * kvcfg.num_kv_layers \
        * kvcfg.page_size * kvcfg.kv_heads * kvcfg.head_dim * 2
    print(f"pool: {kvcfg.num_pages} pages x {kvcfg.page_size} tokens, "
          f"{pool_bytes} bytes of K+V; lanes={LANES} requests={REQUESTS} "
          f"new_tokens={NEW_TOKENS}", flush=True)

    t0 = time.perf_counter()
    params = init_params(cfg, seed=SEED, dtype=jnp.bfloat16)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    mem = dev.memory_stats()
    print(f"weights: {n_bytes} bytes, made from seed {SEED} in "
          f"{time.perf_counter() - t0:.3f} s; peak_bytes_in_use so far "
          f"{mem.get('peak_bytes_in_use')} of bytes_limit "
          f"{mem.get('bytes_limit')}", flush=True)

    runs = {}
    for backend in ("jnp", "kernel"):
        runs[backend] = serve_once(cfg, kvcfg, params, backend, dev)
        gc.collect()             # drop the first engine before the second

    a, b = runs["jnp"], runs["kernel"]
    if a["tokens"] != b["tokens"]:
        raise RuntimeError(f"tokens differ between backends: jnp "
                           f"{a['tokens']} vs kernel {b['tokens']}")
    for field, x in a["alloc"].items():
        if not np.array_equal(x, b["alloc"][field]):
            raise RuntimeError(f"final FreeListState.{field} differs "
                               f"between backends")
    print(f"jnp vs kernel: generated tokens and final FreeListState "
          f"bit-identical ({sum(map(len, a['tokens'].values()))} tokens)",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()

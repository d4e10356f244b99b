"""The model families under ``bench/families/``.

The dense family gives what the benchmark gave before its code moved
there from ``bench/weights.py``, ``bench/reference.py`` and
``bench/flops.py``: the hashes below were recorded at smoke size on the
CPU at commit 54c19b6, before the move, and ``decode_flops`` at the
cell's own size too.  A family is found by the configuration file's
``"family"`` key and nothing else: a copy under another name, in another
directory, runs a cell, and a missing or unknown name is refused.
"""
import hashlib
import io
import shutil

import jax
import numpy as np
import pytest

from bench import spec
from bench.driver import Step
from bench.harness import run_cell
from bench.tests import smoke
from bench.weights import make_weights

CELL = "deepseek-7b.long-decode-sat"
SEED = 2**40 + 54321

WEIGHTS = {
    1: {
        "embed":
            "0f9b08e31b8e6f6546bb70441470724f20d8d501b621d7887ca7e9d7eb929847",
        "final_norm":
            "05b7928d80d3f67d221b07b64c5019a32e3dbfc069b6b1f891b30575fff45a65",
        "layers/attn/wk":
            "8ddcc2fcdd0b17c12d46e118299c4d6c94848d0116ee01e6d8338c60a72eb06f",
        "layers/attn/wo":
            "36195e2b8f3559a1507b41371b50d2f5df683c30eedd8372a80709cc3023584b",
        "layers/attn/wq":
            "29636bf22c7745b64297261672408876538455d82fb5bec90646d7be37ff5172",
        "layers/attn/wv":
            "8cf4b44630490bb907f42faebb5cf152bfa4e45ef0c6b53164bb06c67a841f9d",
        "layers/ln_attn":
            "7264dfe170e871601ab4e1143e05ee9a6c7a877744b96584823f4c27826c4630",
        "layers/ln_mlp":
            "6fe0912c9f04ca9628dba1bdbcadc49a3e03a75135f20a3459a320c8842eb4fe",
        "layers/mlp/w_in":
            "b82ffba0fd725a8bf5d25f2a2092c88b6a6ed836df9ce10cddfcebcfe671cd04",
        "layers/mlp/w_out":
            "c48ef33bfbf2bfc2248d12043a80b2170c59439ab14a589f28cf85d00e8ae5db",
        "unembed":
            "8c0dd97f838e85f65739c68119f462ff5502488a72f9682f2a025464771a104b",
    },
    2**40 + 12345: {
        "embed":
            "011bc601f25dccab36d2f57e6ea6a479544cd023394b6f7499e3e12339e14326",
        "final_norm":
            "cdec648d8fb22beec82862287da70cffde73bac5b7466197d48a9a302f5501d7",
        "layers/attn/wk":
            "17efdca0e22402585c289c6abe317626f7625de953f9632520b4978bf57ab4a3",
        "layers/attn/wo":
            "b389e01090922c59913eb2059cf2c4b3b88d5c2ecb2e6c638631fcf490f08159",
        "layers/attn/wq":
            "26c552b905ea0ebdb28e03def1962c2e60cad332703f708c792ed9a5f833c00c",
        "layers/attn/wv":
            "579950f499406f3c201978ab78492ea3ff1a455392f40895307e16b97d5737f1",
        "layers/ln_attn":
            "8b0649ecb0e9aeb33b2c10c2e73f04a637c14a82bac1b6b2158a012f41f1b083",
        "layers/ln_mlp":
            "47414d8f1e3ce0e5ab1e7fec7c3006bee79204eeb3df18a0cb58cdd960c09fa6",
        "layers/mlp/w_in":
            "5ea388f26b1721544f2c736ca9863f89e70321416e77c6e4a9cec9e6f5c2c82f",
        "layers/mlp/w_out":
            "0f7ddfced168658626d6114112e358099d95ca83225071e72904dda0f4de8759",
        "unembed":
            "b7674ae7b4eecb19869361eb1d51e3fa43ca3ea2389300b263e8d06a1c4d4290",
    },
}
#: forward_gaps over seed 7's weights, the program's targets and the fp8
#: control's (``bench.correct.logit_gaps``'s three passes)
GAPS = {
    "gaps":
        "c92fd54bb44cfdc9ed63b8eccef4a4e205c8b6d80e8de11dc1feda632246f3dc",
    "firsts":
        "917b1e73ea97a80fbcd6b86ea5b3c29c706fe07247f127453459035fbb81ec46",
    "fp8_firsts":
        "c0819db50a448afc30bf43b46302a099e3e209ed132328aab97627c7a675b2bd",
    "ctrl":
        "6706a7c9db1f47d23981b6f2f7a963ee3267c0120555356409eaffe213e168c3",
}
FLOPS = {"smoke": 9248768.0, "cell": 315306803200.0}


def _sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def _dense():
    model = smoke.smoke_model(CELL)
    assert model["family"] == "dense"
    return model, spec.family(model)


@pytest.mark.parametrize("seed", sorted(WEIGHTS))
def test_dense_weights_as_recorded(seed):
    model, _ = _dense()
    flat = jax.tree_util.tree_flatten_with_path(make_weights(model, seed))[0]
    got = {"/".join(k.key for k in path): _sha([x]) for path, x in flat}
    assert got == WEIGHTS[seed]


@pytest.fixture(scope="module")
def gaps():
    model, fam = _dense()
    rng = np.random.default_rng(15)
    v = model["vocab_size"]
    seqs = [rng.integers(0, v, n, dtype=np.int32) for n in (20, 33, 47, 9, 60)]
    targets = [rng.integers(0, v, m, dtype=np.int32)
               for m in (8, 12, 30, 4, 41)]
    w = make_weights(model, 7)
    got, firsts = fam.forward_gaps(model, w, seqs, targets, 128)
    _, f8 = fam.forward_gaps(model, w, seqs, targets, 128, quant="fp8")
    ctrl, _ = fam.forward_gaps(model, w, seqs, f8, 128)
    return {"gaps": got, "firsts": firsts, "fp8_firsts": f8, "ctrl": ctrl}


@pytest.mark.parametrize("name", sorted(GAPS))
def test_dense_reference_as_recorded(gaps, name):
    assert _sha(gaps[name]) == GAPS[name]


def _steps() -> list[Step]:
    lanes = [[40 + 3 * i + s for i in range(8)] for s in range(5)]
    lanes.append([300, 17, 511])
    return [Step(0.01 * k, len(ln), sum(ln), tuple(ln))
            for k, ln in enumerate(lanes)]


@pytest.mark.parametrize("size", sorted(FLOPS))
def test_dense_decode_flops_as_recorded(size):
    model = smoke.smoke_model(CELL) if size == "smoke" \
        else spec.load_cell(CELL).model
    assert spec.family(model).decode_flops(model, _steps()) == FLOPS[size]


def _run(model) -> dict:
    return run_cell(CELL, SEED, 1.0, False, require_tpu=False,
                    compile_cache=False, model=model,
                    mix_spec=smoke.smoke_mix(CELL), log=io.StringIO())


def test_family_found_by_name(tmp_path, monkeypatch):
    """A family file copied under another name into another directory
    runs a cell, with no other change."""
    model = dict(smoke.smoke_model(CELL), family="copied")
    shutil.copy(spec.FAMILY_DIR / "dense.py", tmp_path / "copied.py")
    with pytest.raises(ValueError, match="copied"):
        spec.family(model)
    monkeypatch.setattr(spec, "FAMILY_DIR", tmp_path)
    assert spec.family(model).__file__ == str(tmp_path / "copied.py")
    out = _run(model)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0


@pytest.mark.parametrize("name", [None, "no-such-family", "../reference"])
def test_family_refused(name):
    """No default family, and no file outside ``bench/families/``: the
    refusal names the files that are there."""
    model = smoke.smoke_model(CELL)
    if name is None:
        del model["family"]
    else:
        model["family"] = name
    with pytest.raises(ValueError, match=r"dense\.py"):
        _run(model)

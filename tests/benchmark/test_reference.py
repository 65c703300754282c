"""The plain GPT-2 reference against the program at a tiny size on the CPU,
and the control that ``correct`` has to reject (PERF.md, "How correct is
decided"): the comparison is run through ``benchmark/limits.py``, which
reads the sound runs and the control's in one process, and is held to the
limits the tiny configuration file states."""

import json
import os

import numpy as np
import pytest

from tests.benchmark.helpers import DATA, TINY_MANIFEST, json_lines, run_cli

CELL, CONTROL = "gpt2-tiny.train", "fp8"


def limits_of(cell):
    with open(TINY_MANIFEST) as f:
        m = json.load(f)
    w = {x["name"]: x for x in m["workloads"]}[cell]
    c = {x["name"]: x for x in m["configs"]}[w["config"]]
    with open(os.path.join(DATA, c["file"])) as f:
        cfg = json.load(f)
    return {k: v for k, v in cfg["limits"]["train"].items() if k != "why"}


@pytest.fixture(scope="module")
def readings():
    proc = run_cli("limits.py", "--manifest", TINY_MANIFEST, "--workload", CELL,
                   "--seeds", "11,12,3000000013", "--control-seeds", "11,12,13",
                   "--control", CONTROL)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return [l for l in json_lines(proc) if "seed" in l]


def test_program_matches_reference(readings):
    """The engine's first step on three seeds: loss, gradient norm and the
    way each master weight moved stay under every limit."""
    limits = limits_of(CELL)
    sound = [r for r in readings if r["control"] is None]
    assert len(sound) == 3
    for r in sound:
        assert all(np.isfinite(r[k]) and r[k] <= limits[k] for k in limits), r


def test_control_comes_out_not_correct(readings):
    """The fp8 reference in the program's place breaks the limit on the
    uphill share on every seed, by a factor of 3 or more over the sound
    runs' largest (PERF.md, "How correct is decided")."""
    limits = limits_of(CELL)
    key = "first_step_uphill_share"
    sound = [r[key] for r in readings if r["control"] is None]
    ctl = [r[key] for r in readings if r["control"] == CONTROL]
    assert len(ctl) == 3 and all(v > limits[key] for v in ctl)
    assert min(ctl) >= 3 * max(sound)


def test_control_switch_of_the_command_reports_not_correct():
    proc = run_cli("run.py", "--manifest", TINY_MANIFEST, "--workload", CELL,
                   "--seed", 2147483659, "--control", CONTROL)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["control"] == CONTROL


def test_reference_is_self_contained_float32():
    """It imports nothing of the program and computes in float32 at
    ``highest``; the lower-precision control differs from it; the signs
    it returns are those of its gradient."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import gpt2 as ref
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
    cfg = {"vocab_size": 97, "n_positions": 32, "n_embd": 32, "n_layer": 2, "n_head": 4}
    w = ref.make_weights(ref.key_of(7), cfg, jnp.bfloat16)
    assert ref.make_weights(ref.key_of(7), cfg, jnp.bfloat16)["wq"].tolist() == w["wq"].tolist()
    assert ref.make_weights(ref.key_of(2 ** 31 + 7), cfg, jnp.bfloat16)["wq"].tolist() != w["wq"].tolist()
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 97, (4, 16)), jnp.int32)
    logits = ref.forward(w, ids, 4)
    assert logits.dtype == jnp.float32 and logits.shape == (4, 16, 97)
    # causal: a later token does not move an earlier position's logits
    other = ref.forward(w, ids.at[:, 9].set(3), 4)
    np.testing.assert_array_equal(np.asarray(logits[:, :9]), np.asarray(other[:, :9]))
    loss, gnorm, signs = ref.loss_and_gradient(w, ids, 4)
    w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    g = jax.grad(lambda p: ref.next_token_loss(p, ids, 4))(w32)
    assert float(loss) == pytest.approx(float(ref.next_token_loss(w32, ids, 4)), rel=1e-6)
    assert float(gnorm) == pytest.approx(
        float(jnp.sqrt(sum(jnp.sum(v * v) for v in g.values()))), rel=1e-5)
    assert set(signs) == set(w) and all(v.dtype == jnp.int8 for v in signs.values())
    # the last token predicts nothing and positions 16.. are never used:
    # no gradient there, so no direction
    assert not signs["wpe"][15:].any() and signs["wpe"][:15].all()
    same = np.mean(np.asarray(signs["w_in"]) == np.sign(np.asarray(g["w_in"])))
    assert same > 0.999
    _, gnorm8, signs8 = ref.loss_and_gradient(w, ids, 4, control="fp8")
    assert abs(float(gnorm8 - gnorm)) > 0
    assert 0.001 < np.mean(np.asarray(signs8["w_in"]) != np.asarray(signs["w_in"])) < 0.2
    with pytest.raises(ValueError):
        ref.loss_and_gradient(w, ids, 4, control="bf16")
    # scores are drawn peaked (std about 4), or cached K/V precision hides
    h = jax.random.normal(jax.random.PRNGKey(0), (256, 32))
    q = (h @ w["wq"][0].astype(jnp.float32)).reshape(256, 4, 8)
    k = (h @ w["wk"][0].astype(jnp.float32)).reshape(256, 4, 8)
    scores = jnp.einsum("qnd,knd->nqk", q, k) / np.sqrt(8)
    assert 2.0 < float(jnp.std(scores)) < 8.0

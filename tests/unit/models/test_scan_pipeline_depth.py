"""Prefetch-depth mechanics of ``scan_blocks_pipelined`` (ISSUE 11).

The overlap planner derives ``prefetch_depth=2`` when an entry's
committed map still shows exposed in-scan bytes at depth 1; the model
scan executes it as a TRIPLE-buffered carry (two gathered layers live,
iteration *l* issues layer *l+2*'s gather). Depth is a launch-placement
change only — these tests pin bitwise forward/backward equality against
the depth-1 schedule, the clamp rules, and that the gather hook really
runs two steps ahead.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2_model


def _model_and_inputs(num_layers=4):
    model = gpt2_model("gpt2-tiny", num_layers=num_layers, max_seq_len=32,
                       vocab_size=256, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, 256, size=(2, 16))
    x, positions = model.embed(params, jnp.asarray(ids))
    return model, params, x, positions


def _run(model, params, x, positions, depth):
    out, aux, pullback = model.scan_blocks_pipelined(
        params["blocks"], x, positions,
        gather=lambda t: t, scatter=lambda t: t,
        prefetch_depth=depth)
    dblocks, dx = pullback(jnp.ones_like(out), jnp.zeros(()))
    return out, aux, dblocks, dx


class TestPrefetchDepth:

    def test_depth2_matches_depth1_bitwise(self):
        model, params, x, positions = _model_and_inputs()
        f = jax.jit(lambda p, xx, d: _run(model, p, xx, positions, d),
                    static_argnums=2)
        o1, a1, g1, dx1 = f(params, x, 1)
        o2, a2, g2, dx2 = f(params, x, 2)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        np.testing.assert_array_equal(np.asarray(dx1), np.asarray(dx2))
        for l1, l2 in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))

    def test_depth_clamps_below_three_steps(self):
        # 2 steps: every deep slot would just re-gather the final step —
        # the schedule must silently clamp to 1, not duplicate gathers
        model, params, x, positions = _model_and_inputs(num_layers=2)
        f = jax.jit(lambda p, xx, d: _run(model, p, xx, positions, d),
                    static_argnums=2)
        out1, a1, g1, _ = f(params, x, 1)
        out2, a2, g2, _ = f(params, x, 2)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        for l1, l2 in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))

    def test_depth_zero_rejected(self):
        model, params, x, positions = _model_and_inputs()
        with pytest.raises(ValueError, match="prefetch_depth"):
            model.scan_blocks_pipelined(
                params["blocks"], x, positions,
                gather=lambda t: t, scatter=lambda t: t, prefetch_depth=0)

    def test_depth2_prologue_holds_two_buffers(self):
        """Depth 2 must issue TWO prologue gathers (pf0 + pf1) before any
        compute — the triple-buffer's extra resident layer — while depth
        1 issues one; the scan body traces its gather once either way."""
        model, params, x, positions = _model_and_inputs()

        def count_gathers(depth):
            seen = []
            model.scan_blocks_pipelined(
                params["blocks"], x, positions,
                gather=lambda t: (seen.append(0), t)[1],
                scatter=lambda t: t, prefetch_depth=depth)
            return len(seen)

        assert count_gathers(2) == count_gathers(1) + 1

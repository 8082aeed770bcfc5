"""The zamba2 reference and the ``zamba2-7b.steady`` cell at a CPU size
that keeps the published structure: 16 layers with hybrid points at
irregular gaps, each of the two shared blocks applied twice, two SSM
groups, rank-64 adapters, a tied head and a q8 cache. The reference's
layout is the program's (also at the published widths), the served
weights are the reference's, the reference equals the program's float32
forward, the float8 control departs from it, the reference counts exactly
the products the program serves packed, a whole traced run reads the
recurrent-state metric, and each fault of ``chipbench/faults.py`` planted
under a whole run makes ``correct`` come out false."""
import time

import jax
import numpy as np
import pytest

from chipbench import faults, harness, program_trace, reference, weights
from chipbench.tests import conftest

CELL = "zamba2-7b.steady"
SMOKE = dict(n_layers=16, d_model=128, n_heads=4, n_kv_heads=4, head_dim=64,
             d_ff=256, vocab=256, ssm_state=16, d_inner=256, ssm_groups=2,
             hybrid_layers=[2, 5, 9, 14], adapter_rank=64)
# The widest-gap limit at smoke size, comparing every finished request, from
# CPU readings on seeds 1-6: the program's widest gap reads at most 0.0603,
# the float8 control's at least 0.461.
SMOKE_LIMIT = 0.15


@pytest.fixture
def cell(monkeypatch):
    monkeypatch.setitem(conftest.SMOKE, "zamba2", SMOKE)
    monkeypatch.setitem(conftest.SMOKE_LIMIT, "zamba2", SMOKE_LIMIT)
    return conftest.smoke_cell(CELL)


def _program_shapes(config):
    from repro.models.api import ParamSpec, get_family
    cfg = harness.program_config(config)
    return jax.tree.map(lambda s: tuple(s.shape),
                        get_family(cfg.family).param_specs(cfg),
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def test_layout_is_the_program_layout_at_published_widths(bench):
    cell = harness.load_cell(bench, CELL)
    lay = cell.ref.layout(cell.config["model"])
    assert weights.shapes(lay) == _program_shapes(cell.config)


def test_served_weights_are_the_reference_weights(cell):
    lay = cell.ref.layout(cell.config["model"])
    assert weights.shapes(lay) == _program_shapes(cell.config)
    seed = 2 ** 33 + 7
    dense = weights.make_dense(lay, cell.config["weights"], seed)
    plan, served = harness.served_params(
        lay, weights.make(lay, cell.config["weights"], seed), cell.config)
    requant = jax.jit(plan.quantise)(dense)
    for a, b in zip(jax.tree.leaves(served), jax.tree.leaves(requant)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(dense),
                    jax.tree.leaves(plan.dequantise(served))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reference_matches_the_program_forward_in_float32(cell):
    from repro.models.api import get_family
    model = cell.config["model"]
    w = weights.make_dense(cell.ref.layout(model), cell.config["weights"], 3)
    cfg = harness.program_config(cell.config).replace(
        dtype="float32", kv_format="", remat="none")
    tokens = np.random.default_rng(0).integers(0, model["vocab"], 24)
    with jax.default_matmul_precision("highest"):
        ours = cell.ref.forward(w, tokens, model, reference.exact)
        theirs = get_family(cfg.family).apply(w, {"tokens": tokens[None]}, cfg)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs)[0],
                               rtol=0, atol=2e-4)


def test_fp8_control_departs_from_the_reference(cell):
    model = cell.config["model"]
    w = weights.make_dense(cell.ref.layout(model), cell.config["weights"], 4)
    tokens = np.random.default_rng(1).integers(0, model["vocab"], 24)
    ref = np.asarray(cell.ref.forward(w, tokens, model, reference.exact))
    low = np.asarray(cell.ref.forward(w, tokens, model, reference.fp8))
    err = np.abs(low - ref).max() / np.abs(ref).max()
    # e4m3 keeps 3 mantissa bits: over 16 layers of products the logits
    # move by half their largest value, and still do not blow up
    assert 1e-3 < err < 1.0


def test_matmuls_are_the_packed_products(cell):
    """``matmuls`` lists (calls a step, K, N) of every weight the engine
    serves packed, the tied head included, and nothing it serves dense: a
    layer's Mamba weights, a point's own weights and the head are called
    once, a shared block's once at each hybrid point that applies it."""
    from repro.core.tensor_format import PackedTensor
    model = cell.config["model"]
    points = range(len(model["hybrid_layers"]))
    eng = harness.build_engine(cell, 5)
    flat = jax.tree_util.tree_flatten_with_path(
        eng.params, is_leaf=lambda x: isinstance(x, PackedTensor))[0]
    served = {}
    for path, t in flat:
        if not isinstance(t, PackedTensor):
            continue
        top = path[0].key
        K, N = t.k_dim, int(np.prod(t.out_shape))
        if top == "shared":
            calls = sum(p % model["n_shared_blocks"] == path[1].idx
                        for p in points)
        else:
            calls = 1
        if top == "embed":
            K, N = N, K                     # the tied head: x @ embed.T
        served[(K, N)] = served.get((K, N), 0) + calls
    counted = {}
    for n, K, N in cell.ref.matmuls(model):
        counted[(K, N)] = counted.get((K, N), 0) + n
    assert served == counted


def test_a_traced_run_reads_the_recurrent_state(cell, tmp_path,
                                                monkeypatch):
    """A whole traced run at smoke size on the CPU is correct, and the
    engine's counters give the recurrent state each emitted token cost:
    16 layers of f32 SSM state and bf16 conv state, per slot, read and
    written every step."""
    monkeypatch.setattr(program_trace, "OUT", tmp_path)
    res = harness.run(cell, 2 ** 31 + 3, 1.5, True, time.monotonic(),
                      jax.devices(), tmp_path / f"{CELL}.1")
    assert res["correct"] is True and res["failed"] == 0
    m = res["metrics"]
    per_token = m["recurrent_state_mb_per_token"]["value"]
    model = cell.config["model"]
    per_slot = model["n_layers"] * (
        model["d_inner"] // 64 * 64 * model["ssm_state"] * 4
        + (model["conv_kernel"] - 1) * (
            model["d_inner"] + 2 * model["ssm_groups"] * model["ssm_state"])
        * 2)
    step = 2 * cell.mix["slots"] * per_slot / 1e6
    # a step emits at most one token a slot, and a prefill step none
    assert per_token >= step / cell.mix["slots"]
    assert 0 < m["valid_token_share"]["value"] < 100


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_fails_the_check(cell, fault):
    """A step that keeps its SSM, conv and KV state unchanged, half of the
    batch fed token 0, or a served token altered: each is caught by the
    widest-gap comparison."""
    undo = faults.plant(fault)
    try:
        res = harness.run(cell, 7, 1.5, False, time.monotonic(),
                          jax.devices(), None)
    finally:
        undo()
    gap, limit = res["checks"]["widest_gap"]
    assert gap > limit
    assert res["correct"] is False

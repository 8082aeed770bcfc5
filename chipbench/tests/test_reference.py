"""The references and the weights they read: the weights are made in the
served format, the program decodes them to the reference's values, and each
reference's logits equal the program's own float32 forward on them (the
reference follows the equations the program states)."""
import jax
import numpy as np
import pytest

from chipbench import harness, reference, weights
from chipbench.tests.conftest import smoke_cell

CELLS = ["internlm2-1.8b.steady", "rwkv6-1.6b.steady"]


@pytest.mark.parametrize("name", CELLS)
def test_served_weights_are_the_reference_weights(name):
    """The codes and scales the harness makes are what the program's own
    quantiser makes of the values the reference reads, and the program
    decodes them back to exactly those values."""
    from repro.core import build_plan
    cell = smoke_cell(name)
    lay = cell.ref.layout(cell.config["model"])
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
    # and the grid is the program's: its codebook and block
    fmt = build_plan(dense, cell.config["weights"]["format"]).formats[
        "['embed']"]
    assert fmt.scaling.block_size == cell.config["weights"]["block"]
    np.testing.assert_array_equal(
        np.float32(fmt.element.codepoints),
        np.float32(cell.config["weights"]["codepoints"]))


def test_weights_are_deterministic_and_seeded():
    cell = smoke_cell(CELLS[0])
    lay = cell.ref.layout(cell.config["model"])
    a = weights.make_dense(lay, cell.config["weights"], 5)["layers"]["wq"]
    b = weights.make_dense(lay, cell.config["weights"], 5)["layers"]["wq"]
    c = weights.make_dense(lay, cell.config["weights"], 6)["layers"]["wq"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


@pytest.mark.parametrize("name", CELLS)
def test_reference_matches_the_program_forward_in_float32(name):
    from repro.models.api import get_family
    cell = smoke_cell(name)
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


@pytest.mark.parametrize("name", CELLS)
def test_fp8_control_departs_from_the_reference(name):
    cell = smoke_cell(name)
    model = cell.config["model"]
    w = weights.make_dense(cell.ref.layout(model), cell.config["weights"], 4)
    tokens = np.random.default_rng(1).integers(0, model["vocab"], 24)
    ref = np.asarray(cell.ref.forward(w, tokens, model, reference.exact))
    low = np.asarray(cell.ref.forward(w, tokens, model, reference.fp8))
    err = np.abs(low - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5

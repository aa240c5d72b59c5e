"""The float32 reference of the dense family against the program's own
forward pass, at ``.reduced()`` widths on the CPU."""

import ast
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from _chipbench_small import small_config  # noqa: E402
from chipbench.reference.dense import DenseReference  # noqa: E402
from chipbench.weights import make_params  # noqa: E402
from repro.models.model import Model  # noqa: E402

REFERENCE_DIR = Path(__file__).resolve().parents[2] / "chipbench" / "reference"


def _logits(ref, params, tokens):
    rows = np.arange(len(tokens))
    xn = np.asarray(ref.hidden(tokens, rows))
    head = np.asarray(params["embed"], np.float32)
    return xn @ head.T


@pytest.mark.parametrize("name", ["qwen2-0.5b", "phi4-mini-3.8b"])
def test_reference_agrees_with_the_program_forward(name):
    cfg, mc = small_config(name)
    model = Model(mc)
    params = make_params(jax.eval_shape(model.init, jax.random.key(0)), 7)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    prog, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]})
    prog = np.asarray(prog[0], np.float32)
    ref = _logits(DenseReference(cfg, params), params, tokens)
    ctl = _logits(DenseReference(cfg, params, precision="fp8"), params, tokens)
    # logits spread ~1 and reach ~4, where one bf16 step is 1/64; the
    # program rounds activations and logits to bf16 (8 significant bits),
    # so its logits lie within a few such steps of the reference's (0.03 to
    # 0.09 seen on seeds 7-9). fp8 keeps 4 significant bits and lies ~10x
    # further off on average: the comparison can tell the two apart.
    prog_err = np.abs(prog - ref)
    assert prog_err.max() < 0.15, prog_err.max()
    assert (prog.argmax(-1) == ref.argmax(-1)).mean() >= 0.9
    assert np.abs(ctl - ref).mean() > 3 * prog_err.mean()


def test_reference_is_causal_and_pads_without_effect():
    cfg, mc = small_config("qwen2-0.5b")
    params = make_params(jax.eval_shape(Model(mc).init, jax.random.key(0)), 3)
    ref = DenseReference(cfg, params)
    tokens = np.random.default_rng(1).integers(0, cfg["vocab_size"], 30)
    whole = np.asarray(ref.hidden(tokens, np.arange(20)))
    prefix = np.asarray(ref.hidden(tokens[:20], np.arange(20)))
    np.testing.assert_allclose(whole, prefix, rtol=1e-5, atol=1e-5)


def test_weights_are_seeded_and_in_the_served_dtype():
    _, mc = small_config("qwen2-0.5b")
    layout = jax.eval_shape(Model(mc).init, jax.random.key(0))
    a, b = make_params(layout, 2**31 + 5), make_params(layout, 2**31 + 5)
    c = make_params(layout, 5)
    for x, y, z, s in zip(*(jax.tree.leaves(t) for t in (a, b, c, layout))):
        assert x.dtype == s.dtype and x.shape == s.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(c["embed"]))
    # q/k/v biases are drawn, not zero, so the reference checks that path
    assert float(jnp.abs(a["layers"]["attn"]["bq"]).max()) > 0


def test_reference_imports_nothing_of_the_program():
    for path in REFERENCE_DIR.glob("*.py"):
        tree = ast.parse(path.read_text())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module or "" for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom)]
        assert not [m for m in names if m.split(".")[0] == "repro"], path

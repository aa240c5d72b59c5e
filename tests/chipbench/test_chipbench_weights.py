"""The benchmark's weight draws: the dense layouts draw the same bits as
before family files had draws of their own, and a leaf that no table
draws is an error."""

import hashlib
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from _chipbench_small import small_config  # noqa: E402
from chipbench.weights import make_params  # noqa: E402
from repro.configs import get  # noqa: E402
from repro.models.model import Model  # noqa: E402

SEED = 2**31 + 11
# The first 16 hex digits of the sha256 of each leaf's bytes at SEED, at
# .reduced() widths, as drawn when the dense leaves were the only ones the
# benchmark could draw.
PINNED = {
    "qwen2-0.5b": {
        "embed": "ca003f8bc9aa7c3d",
        "final_norm/w": "bdcfeac75230654c",
        "layers/attn/bk": "cff61599443c4bca",
        "layers/attn/bq": "ad5f0cf0e75f74a4",
        "layers/attn/bv": "29fba1f95548ef21",
        "layers/attn/wk": "4f38fedf389038ba",
        "layers/attn/wo": "fbb72dffd565d1c6",
        "layers/attn/wq": "ea4ff8bd225411e2",
        "layers/attn/wv": "c455f70922f489ff",
        "layers/attn_norm/w": "c91bb1b590ae8e46",
        "layers/mlp/wg": "214ff1573d084ac3",
        "layers/mlp/wi": "ceaabae694e73f49",
        "layers/mlp/wo": "dead286e87d9892b",
        "layers/mlp_norm/w": "02aae44276c24e7f",
    },
    "phi4-mini-3.8b": {
        "embed": "ca003f8bc9aa7c3d",
        "final_norm/w": "bdcfeac75230654c",
        "layers/attn/wk": "41093d45481627f1",
        "layers/attn/wo": "c8a54a4675dd63c7",
        "layers/attn/wq": "1d16317f6ebb22d3",
        "layers/attn/wv": "4f38fedf389038ba",
        "layers/attn_norm/w": "5dc7ddb83772793a",
        "layers/mlp/wg": "d8f684839b0d68ec",
        "layers/mlp/wi": "a07d607446e54650",
        "layers/mlp/wo": "b026a786445f6923",
        "layers/mlp_norm/w": "252c98ef94ea640f",
    },
}


def _layout(mc):
    return jax.eval_shape(Model(mc).init, jax.random.key(0))


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_draws_are_pinned(name):
    _, mc = small_config(name)
    params = make_params(_layout(mc), SEED)
    got = {"/".join(k.key for k in path):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
           for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == PINNED[name]


def test_a_leaf_in_no_table_is_an_error_naming_its_path():
    layout = _layout(get("phi3.5-moe-42b-a6.6b").reduced())
    with pytest.raises(ValueError, match="layers/moe/router"):
        make_params(layout, SEED)
    with pytest.raises(ValueError, match="layers/moe/router"):
        make_params(layout, SEED, {"a_log": lambda k, s, d: None})


def test_shared_draws_come_before_the_family_s():
    """A family's table draws only what the shared one does not: the
    untied head is shared, so a family entry for it is an error, not a
    law that is silently never used."""
    layout = _layout(get("phi3.5-moe-42b-a6.6b").reduced())
    ones = {"router": lambda k, s, d: jax.numpy.ones(s, d)}
    params = make_params(layout, SEED, ones)
    assert np.all(np.asarray(params["layers"]["moe"]["router"]) == 1)
    head = np.asarray(params["head"], np.float32)
    d = head.shape[0]
    assert abs(head.std() * d**0.5 - 1) < 0.1  # N(0, 1/fan_in)
    with pytest.raises(ValueError, match="head, wo"):
        make_params(layout, SEED, dict(ones, head=ones["router"], wo=ones["router"]))

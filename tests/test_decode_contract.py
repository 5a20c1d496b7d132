"""The decode contract (tpu_pipelines/models/decode_contract.py): the one
type between the served models and the engine, and the direction of the
dependency."""

import ast
import os

import numpy as np
import pytest

import tpu_pipelines.models as models_pkg

pytestmark = pytest.mark.generative

MODELS_DIR = os.path.dirname(models_pkg.__file__)


def test_no_model_imports_the_serving_layer():
    found = []
    for name in sorted(os.listdir(MODELS_DIR)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(MODELS_DIR, name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            found += [
                (name, node.lineno, n) for n in names
                if n.startswith("tpu_pipelines.serving")]
    assert not found


def _fn(*args):
    return None


@pytest.mark.parametrize("prefills", [
    {},
    dict(prefill=_fn, prefill_window=_fn, prefill_window_len=8,
         blank_cache=_fn),
], ids=["neither", "both"])
def test_exactly_one_prefill(prefills):
    from tpu_pipelines.models.decode_contract import DecodeContract

    with pytest.raises(ValueError, match="exactly one"):
        DecodeContract(
            step=_fn, max_decode_len=4, eos_id=1, pad_id=0, **prefills)


def _t5_contract():
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models.t5 import T5, make_continuous_decode_fns

    model = T5(
        vocab_size=48, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, dropout_rate=0.0, dtype=jnp.float32)
    fns = make_continuous_decode_fns(model, max_decode_len=8, max_input_len=6)
    inputs = jnp.ones((1, 6), jnp.int32)
    params = jax.eval_shape(
        model.init, jax.random.key(0),
        {"inputs": inputs, "targets": inputs})["params"]
    cache, _, _ = jax.eval_shape(fns.prefill, params, inputs, inputs)
    return fns, cache


def _windowed_contract(name):
    import importlib

    tiny = importlib.import_module(f"test_{name}")   # the model's own tests
    module = importlib.import_module(f"tpu_pipelines.models.{name}")
    model = getattr(module, f"build_{name}_model")(
        {**tiny.HP, "compute_dtype": "float32", "param_dtype": "float32"})
    fns = tiny.decode_fns(model)     # the module's builder at the tiny size
    return fns, fns.blank_cache(2)


@pytest.mark.parametrize(
    "name", ["t5", "evabyte", "pangu_moe", "xing", "command_a", "keye"])
def test_every_builder_returns_the_contract_and_names_every_leaf(name):
    import jax

    from tpu_pipelines.models.decode_contract import CacheKind, DecodeContract

    fns, cache = _t5_contract() if name == "t5" else _windowed_contract(name)
    assert type(fns) is DecodeContract
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    assert leaves
    for path, _ in leaves:
        assert isinstance(fns.cache_kinds[fns.cache_kind_of(path)], CacheKind)
    if name != "t5":
        # the decoder-only family: the prompt's length, no encoder rows
        assert fns.prefill is None and fns.encoded_shape == (0,)
        assert int(fns.first_decode_pos(np.array([[1, 1, 0]]))) == 2

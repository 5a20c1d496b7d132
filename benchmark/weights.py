"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program's initialiser: the same
seed gives the program and the plain reference the same numbers, and the
reference takes nothing the program has made.  The rule for each leaf is
data, in the configuration file under ``weights``: the first rule whose
key ends the leaf's path gives the standard deviation, as a number, or as
``"fan_in"`` for ``fan_in ** -0.5``; ``"around_one"`` centres a norm's scale
on one.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A key from any whole number: ``--seed`` may pass 2**31.  The ``rbg``
    generator is one device operation per draw, where threefry is a page
    of arithmetic that the compiler takes minutes over at 738 M values."""
    import jax

    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(4)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32), impl="rbg")


def leaf_path(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def flat_leaves(tree) -> Dict[str, Any]:
    """``{leaf path: leaf}`` of a parameter tree."""
    import jax

    return {
        leaf_path(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def _rule_for(path: str, rules: Dict[str, Any]) -> Tuple[str, Any]:
    for suffix, rule in rules.items():
        if path.endswith(suffix):
            return suffix, rule
    raise KeyError(f"no weights rule ends the path {path!r}")


def _fan_in(path: str, shape: Tuple[int, ...]) -> int:
    # An attention output projection contracts (heads, head_dim); every
    # other kernel contracts its first axis.
    if path.endswith("out/kernel") and len(shape) == 3:
        return int(shape[0] * shape[1])
    return int(shape[0])


def make_weights(shapes: Any, rules: Dict[str, Any], seed: int):
    """A tree like ``shapes`` (of ``ShapeDtypeStruct``), filled from the
    seed under one ``jit``."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    plan = []
    for path, leaf in flat:
        p = leaf_path(path)
        _, rule = _rule_for(p, rules)
        if rule == "fan_in":
            mean, std = 0.0, _fan_in(p, leaf.shape) ** -0.5
        elif rule == "around_one":
            mean, std = 1.0, 0.02
        else:
            mean, std = 0.0, float(rule)
        plan.append((tuple(leaf.shape), jnp.dtype(leaf.dtype), mean, std))

    # Leaves that share a shape and a rule (one per layer) are drawn
    # together, so the program is a few dozen draws and not one a leaf.
    groups: Dict[Tuple, list] = {}
    for i, spec in enumerate(plan):
        groups.setdefault(spec, []).append(i)

    def build(key):
        leaves = [None] * len(plan)
        for g, ((shape, dtype, mean, std), members) in enumerate(
                groups.items()):
            draw = jax.random.normal(
                jax.random.fold_in(key, g), (len(members),) + tuple(shape),
                jnp.float32,
            )
            for j, i in enumerate(members):
                leaves[i] = (mean + std * draw[j]).astype(dtype)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))

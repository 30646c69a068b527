"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, in the program's parameter tree
and in the type they are served in, so that the plain reference can read
the same numbers without taking anything the program made.  The tree's
shapes come from the program's own schema (``jax.eval_shape``, nothing
is computed); every value comes from here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# fan-in axes of each matrix leaf: a normal draw is divided by the square
# root of their product, so every layer's output keeps unit scale
FAN_IN = {"wq": (-3,), "wk": (-3,), "wv": (-3,), "wo": (-3, -2),
          "gate": (-2,), "up": (-2,), "down": (-2,)}
NORMS = ("scale", "q_norm", "k_norm")
EMBED_STD = 0.02
NORM_JITTER = 0.1


def seed_key(seed: int):
    """A threefry key from any whole number (larger than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make_params(abstract, seed: int):
    """``abstract``: the program's parameter tree of ShapeDtypeStructs.
    -> the same tree of device arrays, drawn from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    specs = [(_leaf_name(p), a.shape, a.dtype) for p, a in flat]
    for name, _, _ in specs:
        if name not in FAN_IN and name not in NORMS and name != "tokens":
            raise KeyError(f"no initialiser for parameter leaf {name!r}")

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(specs))
        out = []
        for k, (name, shape, dtype) in zip(keys, specs):
            z = jax.random.normal(k, shape, jnp.float32)
            if name == "tokens":
                v = z * EMBED_STD
            elif name in NORMS:
                v = 1.0 + NORM_JITTER * z
            else:
                fan = int(np.prod([shape[a] for a in FAN_IN[name]]))
                v = z / np.sqrt(fan)
            out.append(v.astype(dtype))
        return out

    return jax.tree_util.tree_unflatten(treedef, init(seed_key(seed)))


def reference_view(params) -> dict:
    """The reference's layout of the same arrays (no copies): a dense
    decoder whose layers the program stacks under ``stack/periods/slot0``
    (one layer kind, so one slot and no remainder)."""
    stack = params["stack"]
    if stack.get("rem") or list(stack["periods"]) != ["slot0"]:
        raise ValueError("reference view expects one stacked layer slot")
    blk = stack["periods"]["slot0"]
    layers = {"attn_norm": blk["norm1"]["scale"],
              "mlp_norm": blk["norm2"]["scale"]}
    for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm"):
        if k in blk["mix"]:
            layers[k] = blk["mix"][k]
    for k in ("gate", "up", "down"):
        layers[k] = blk["ffn"][k]
    return {"embed": params["embed"]["tokens"],
            "final_norm": params["final_norm"]["scale"], "layers": layers}

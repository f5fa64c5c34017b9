"""Random weights from ``--seed``, drawn one layer at a time on the device.

The float draws are defined here once, as functions of the seed, the layer
index and the configuration's sizes. The served weights are built from them
by :func:`build_served`: one jitted call that draws one layer's float
weights at a time (``lax.map``), passes each matrix through the program's
own packing function and stacks the packed layers, so no float copy of the
whole model ever exists. The reference draws the same floats again, layer
by layer, and ternarises them with its own code.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ATTN = ("q", "k", "v", "o")
FFN = ("gate", "up", "down")


def dims(c: dict) -> dict:
    """The sizes the draws need, from a configuration file's keys."""
    return {"d": c["hidden_size"], "layers": c["num_hidden_layers"],
            "h": c["num_attention_heads"], "hk": c["num_key_value_heads"],
            "hd": c["head_dim"], "ff": c["intermediate_size"],
            "vocab": c["vocab_size"]}


def matrix_shapes(c: dict) -> dict:
    """(n_in, n_out) of every ternary matrix of one layer."""
    m = dims(c)
    d, q, kv, ff = m["d"], m["h"] * m["hd"], m["hk"] * m["hd"], m["ff"]
    return {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d),
            "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, including ones past 32 bits."""
    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def draw_layer(key, i, c: dict) -> dict:
    """Layer ``i``'s float32 matrices: normal / sqrt(fan_in)."""
    lk = jax.random.fold_in(key, 1000 + i)
    out = {}
    for j, (name, (n_in, n_out)) in enumerate(matrix_shapes(c).items()):
        out[name] = (jax.random.normal(jax.random.fold_in(lk, j),
                                       (n_in, n_out), jnp.float32)
                     / math.sqrt(n_in))
    return out


def draw_embed(key, c: dict) -> jax.Array:
    m = dims(c)
    return jax.random.normal(jax.random.fold_in(key, 1),
                             (m["vocab"], m["d"]), jnp.float32) * 0.02


def draw_head(key, c: dict) -> jax.Array:
    m = dims(c)
    return (jax.random.normal(jax.random.fold_in(key, 2),
                              (m["d"], m["vocab"]), jnp.float32)
            / math.sqrt(m["d"]))


def served_fn(c: dict, pack):
    """``key -> packed parameter tree``, the tree the program serves.

    ``pack`` is the program's packing function for one float matrix
    (``repro.core.bitlinear.pack_params``)."""
    m = dims(c)

    def build(key):
        ones = jnp.ones((m["d"],), jnp.float32)

        def layer(i):
            w = draw_layer(key, i, c)
            return {"ln1": {"gamma": ones}, "ln2": {"gamma": ones},
                    "attn": {n: pack(w[n]) for n in ATTN},
                    "ffn": {n: pack(w[n]) for n in FFN}}

        return {"embed": {"table": draw_embed(key, c)},
                "blocks": {"b0": jax.lax.map(layer, jnp.arange(m["layers"]))},
                "final_norm": {"gamma": ones},
                "lm_head": {"w": draw_head(key, c)}}

    return build


def build_served(c: dict, seed: int, pack) -> dict:
    """The served parameters, made on the device in one jitted call."""
    return jax.block_until_ready(jax.jit(served_fn(c, pack))(seed_key(seed)))

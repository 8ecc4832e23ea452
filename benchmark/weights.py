"""Seeded parameters and filler rows, made on the device in jitted calls.

The benchmark makes the weights, not the program: one jitted call draws the
whole tree from ``--seed`` in the type it is served in (float32 parameters)
and lays it out under the names the program's flax modules use, so that
``TPUEncoderEmbedder(..., params=tree)`` takes it as it would a checkpoint.
The plain reference is given the same tree.

The seed enters as two uint32 words (any whole number up to 2**64 fits),
traced, so that one compiled program serves every seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any non-negative whole number and a stream id."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "layers", "heads", "mlp", "max_len", "types"))
def _draw(key, *, vocab, hidden, layers, heads, mlp, max_len, types):
    head_dim = hidden // heads
    names = ["word", "position", "type", "query", "key", "value", "out", "up", "down", "bias"]
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def normal(k, shape, std):
        return jax.random.normal(k, shape, jnp.float32) * std

    fan_in = 1.0 / np.sqrt(hidden)
    stacked = {
        "query": normal(ks["query"], (layers, hidden, heads, head_dim), fan_in),
        "key": normal(ks["key"], (layers, hidden, heads, head_dim), fan_in),
        "value": normal(ks["value"], (layers, hidden, heads, head_dim), fan_in),
        "out": normal(ks["out"], (layers, heads, head_dim, hidden), fan_in),
        "up": normal(ks["up"], (layers, hidden, mlp), fan_in),
        "down": normal(ks["down"], (layers, mlp, hidden), 1.0 / np.sqrt(mlp)),
    }
    bias_keys = jax.random.split(ks["bias"], 4)
    small = {
        "qkv_bias": normal(bias_keys[0], (layers, 3, heads, head_dim), 0.02),
        "out_bias": normal(bias_keys[1], (layers, hidden), 0.02),
        "up_bias": normal(bias_keys[2], (layers, mlp), 0.02),
        "down_bias": normal(bias_keys[3], (layers, hidden), 0.02),
    }
    ones = jnp.ones((hidden,), jnp.float32)
    zeros = jnp.zeros((hidden,), jnp.float32)
    tree = {
        "embeddings": {
            "word": {"embedding": normal(ks["word"], (vocab, hidden), 1.0)},
            "position": {"embedding": normal(ks["position"], (max_len, hidden), 0.1)},
            "type": {"embedding": normal(ks["type"], (types, hidden), 0.1)},
            "ln": {"scale": ones, "bias": zeros},
        }
    }
    for i in range(layers):
        tree[f"layer_{i}"] = {
            "attention": {
                "query": {"kernel": stacked["query"][i], "bias": small["qkv_bias"][i, 0]},
                "key": {"kernel": stacked["key"][i], "bias": small["qkv_bias"][i, 1]},
                "value": {"kernel": stacked["value"][i], "bias": small["qkv_bias"][i, 2]},
                "out": {"kernel": stacked["out"][i], "bias": small["out_bias"][i]},
            },
            "attention_ln": {"scale": ones, "bias": zeros},
            "mlp_up": {"kernel": stacked["up"][i], "bias": small["up_bias"][i]},
            "mlp_down": {"kernel": stacked["down"][i], "bias": small["down_bias"][i]},
            "mlp_ln": {"scale": ones, "bias": zeros},
        }
    return {"params": tree}


def make_params(model: dict, seed: int):
    """The encoder's parameter tree for a configuration file's ``model``
    group, drawn from ``seed`` on the default device."""
    return _draw(
        seed_key(seed, stream=1),
        vocab=model["vocab_size"],
        hidden=model["hidden_size"],
        layers=model["num_hidden_layers"],
        heads=model["num_attention_heads"],
        mlp=model["intermediate_size"],
        max_len=model["max_position_embeddings"],
        types=model["type_vocab_size"],
    )


@functools.partial(jax.jit, static_argnames=("rows", "dim"))
def _filler_block(key, block, *, rows, dim):
    x = jax.random.normal(jax.random.fold_in(key, block), (rows, dim), jnp.float32)
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


def filler_block(seed: int, block: int, rows: int, dim: int):
    """Block ``block`` of the filler: ``rows`` unit vectors, float32, on the
    device.  Same seed and block, same rows: the slab and the reference each
    call this and share nothing else."""
    return _filler_block(seed_key(seed, stream=2), jnp.uint32(block), rows=rows, dim=dim)

"""The decoder of grouped-query attention with window and global layers and a
router before attention, at a toy size, for the tests of the architecture
(``test_window_moe_decoder.py``) and of the executor over every architecture
(``test_decoder.py``): one period of four layers (global, then three window
layers), hidden 64, 4 query heads over 2 K/V heads of 16, a window of 16
keys (two key blocks of 8), 8 ReGLU experts of 32 with 2 a token."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import smallthinker as family
from pathway_tpu.models import window_moe_decoder

GROUP = {
    "family": "smallthinker", "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "experts_held": 8, "expert_offset": 0, "rope_theta": 10000.0, "rope_layout": [0, 1, 1, 1],
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 16, "rms_norm_eps": 1e-6, "vocab_size": 1280,
    "max_position_embeddings": 48, "tie_word_embeddings": False, "param_dtype": "float32",
}
POSITIONS = 48


def config_of(group: dict, **over) -> window_moe_decoder.WindowMoEDecoderConfig:
    fields = {f.name for f in dataclasses.fields(window_moe_decoder.WindowMoEDecoderConfig)} & set(group)
    given = {k: tuple(group[k]) if isinstance(group[k], list) else group[k] for k in fields}
    return window_moe_decoder.WindowMoEDecoderConfig(**{**given, "dtype": jnp.float32, "key_block": 8, "expert_block": 4, **over})


def float32_params(group: dict, seed: int = 7):
    """The family's draw in float32, the norms moved off their resting values
    so that leaving one out shows, and the embedding at unit spread so that
    the router, which reads the layer's input, is not near uniform."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), family.make_params(group, seed))
    rng = np.random.default_rng(seed)
    moved = lambda v: v + jnp.asarray(rng.normal(0, 0.1, v.shape), jnp.float32)
    for lp in params["layers"]:
        lp["attn_norm"], lp["mlp_norm"] = moved(lp["attn_norm"]), moved(lp["mlp_norm"])
    params["final_norm"] = moved(params["final_norm"])
    params["embed"] = params["embed"] * 50.0
    return params

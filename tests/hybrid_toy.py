"""The decoder-hybrid-decoder at a toy size, for the tests of the
architecture (``test_hybrid_decoder.py``) and of the executor over both
architectures (``test_decoder.py``): 8 layers (Mamba 0, 2, 4; window 1, 3;
full 5; gated memory 6; cross 7), hidden 64, 8 query and 4 key heads of 8, a
window of 8 keys, 4 states a channel."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import phi4flash as family
from pathway_tpu.models import hybrid_decoder

GROUP = {
    "family": "phi4flash", "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 128, "sliding_window": 8, "mb_per_layer": 2, "layer_norm_eps": 1e-5, "vocab_size": 1280,
    "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4, "param_dtype": "float32",
}
POSITIONS = 48


def config_of(group: dict, **over) -> hybrid_decoder.HybridDecoderConfig:
    fields = {k: v for k, v in group.items() if k not in ("family", "param_dtype")}
    return hybrid_decoder.HybridDecoderConfig(**{**fields, "vocab_held": group["vocab_size"], "dtype": jnp.float32, "key_block": 8, **over})


def float32_params(group: dict, seed: int = 7):
    """The family's draw in float32, every norm, bias and per-channel vector
    moved off its resting value so that leaving one out shows."""
    rng = np.random.default_rng(seed)
    vectors = ("scale", "bias", "conv_b", "D", "subln", "qkv_b", "q_b", "o_b")

    def moved(path, a):
        a = a.astype(jnp.float32)
        name = getattr(path[-1], "key", None)
        return a + jnp.asarray(rng.normal(0, 0.1, a.shape), jnp.float32) if name in vectors else a

    return jax.tree_util.tree_map_with_path(moved, family.make_params(group, seed))

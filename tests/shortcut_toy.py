"""The decoder of double layers with a shortcut-connected routed branch at a
toy size, for the tests of the architecture (``test_shortcut_moe_decoder.py``)
and of the executor over all three architectures (``test_decoder.py``): two
double layers (a branch's return and the next layer's first attention are
both crossed), hidden 64, 4 heads of 16 + 8, a router over 16 routed and 8
zero-computation experts with 4 a token."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import longcat_flash as family
from pathway_tpu.models import shortcut_moe_decoder

GROUP = {
    "family": "longcat_flash", "hidden_size": 64, "num_layers": 2, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "n_routed_experts": 16,
    "n_routed_experts_published": 16, "expert_offset": 0, "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 4,
    "routed_scaling_factor": 6.0, "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "rope_theta": 1e7, "rms_norm_eps": 1e-5, "vocab_size": 1280,
    "vocab_size_published": 1280, "param_dtype": "float32",
}
POSITIONS = 48


def config_of(group: dict, **over) -> shortcut_moe_decoder.ShortcutMoEDecoderConfig:
    same = {f.name for f in dataclasses.fields(shortcut_moe_decoder.ShortcutMoEDecoderConfig)} & set(group) - {"n_routed_experts", "vocab_size"}
    return shortcut_moe_decoder.ShortcutMoEDecoderConfig(
        **{k: group[k] for k in same}, n_routed_experts=group["n_routed_experts_published"], experts_held=group["n_routed_experts"],
        vocab_size=group["vocab_size_published"], vocab_held=group["vocab_size"],
        **{"dtype": jnp.float32, "key_block": 8, "expert_block": 4, **over},
    )


def float32_params(group: dict, seed: int = 7):
    """The family's draw in float32, norms and the router's bias moved off
    their resting values so that leaving one out shows."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), family.make_params(group, seed))
    rng = np.random.default_rng(seed)
    moved = lambda v, spread=0.1: v + jnp.asarray(rng.normal(0, spread, v.shape), jnp.float32)
    for lp in params["layers"]:
        for ap in lp["attn"]:
            ap.update({name: moved(ap[name]) for name in ("attn_norm", "q_norm", "kv_norm")})
        lp["mlp_norm"] = [moved(v) for v in lp["mlp_norm"]]
        lp["router_bias"] = moved(lp["router_bias"], 0.01)
    params["final_norm"] = moved(params["final_norm"])
    return params

"""The generation stage's third architecture at a toy size on the CPU: the
decoder of double layers with a shortcut-connected routed branch
(``models/shortcut_moe_decoder.py``), held against the plain reference of
the benchmark's ``longcat_flash`` family (float32 ``jax.numpy``, the whole
sequence at once, no cache, no chunks, no absorbed form), on seeded weights;
(``JittedDecoder``, ``TPUDecoderChat`` and the answer route run over all
three architectures in ``test_decoder.py``: ``served``); and the latent-attention
core it shares with ``models/decoder.py``: that module's programs are what
they were before the core was split out (the fused kernel's compile for a v5e
at this model's widths stands in ``test_decoder.py``: one file loads libtpu)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import deepseek_v32 as deepseek_family
from benchmark.families import longcat_flash as family
from pathway_tpu.internals import device_counters as devctr
from pathway_tpu.models import decoder as mla_decoder
from pathway_tpu.models import shortcut_moe_decoder as decoder
from pathway_tpu.parallel import JittedDecoder
from tests.shortcut_toy import GROUP, POSITIONS, config_of, float32_params

#: float32 program against float32 reference: what is left is the order of summation (measured 4e-6 on logits of unit spread)
TOLERANCE = 2e-5


@pytest.fixture(scope="module")
def model():
    cfg = config_of(GROUP)
    params = float32_params(GROUP)
    ids = np.random.default_rng(0).integers(1000, GROUP["vocab_size"], size=44).astype(np.int32)
    return {
        "cfg": cfg, "params": params, "ids": ids,
        "reference": family.reference_logits(params, GROUP, [ids], [list(range(ids.size))], q_block=16)[0],
        "prefill": jax.jit(decoder.prefill, static_argnames=("config",)),
        "decode": jax.jit(decoder.decode_step, static_argnames=("config",)),
    }


def _prefill(model, cache, slot, start, tokens, bucket, cfg=None, params=None):
    ids = np.zeros(bucket, np.int32)
    ids[: len(tokens)] = tokens
    return model["prefill"](params or model["params"], jnp.asarray(ids), cache, slot, start, len(tokens), config=cfg or model["cfg"])


def _decode(model, cache, slot, position, token, cfg=None, params=None):
    logits, cache, stats = model["decode"](
        params or model["params"], jnp.asarray([token]), cache, jnp.asarray([slot]), jnp.asarray([position]), config=cfg or model["cfg"]
    )
    return logits[0], cache, stats


def test_the_built_configuration_is_the_groups_and_the_preset_is_the_published_one(model):
    from pathway_tpu.xpacks.llm.llms import decoder_preset

    assert family.built_differs(GROUP, model["cfg"]) == {}
    assert "ffn_hidden_size" in family.built_differs(GROUP, dataclasses.replace(model["cfg"], ffn_hidden_size=256))
    published = decoder_preset("meituan-longcat/LongCat-Flash-Chat")
    assert published is decoder.LONGCAT_FLASH_CHAT is decoder_preset("longcat-flash-chat")
    assert (published.num_layers, published.n_routed_experts, published.zero_expert_num, published.moe_topk, published.vocab_held) == (28, 512, 256, 12, 131072)
    assert (published.q_lora_scale, published.kv_lora_scale**2, published.softmax_scale) == (2.0, pytest.approx(12.0), 192**-0.5)
    with pytest.raises(ValueError, match="identity"):
        dataclasses.replace(published, zero_expert_type="copy")


def test_prefill_then_decode_through_the_caches_is_the_references_full_forward(model):
    ids, ref = model["ids"], model["reference"]
    cache = decoder.init_cache(model["cfg"], 2, POSITIONS)
    assert {k: v.shape for k, v in cache.items()} == {"latent": (4, 2, POSITIONS, 24)}  # a cache a sublayer, two a layer
    logits, cache, _ = _prefill(model, cache, 1, 0, ids[:24], 24)
    assert np.abs(np.asarray(logits) - ref[23]).max() < TOLERANCE
    for t in range(24, ids.size):
        logits, cache, _ = _decode(model, cache, 1, t, ids[t])
        assert np.abs(np.asarray(logits) - ref[t]).max() < TOLERANCE, t
    assert float(jnp.abs(cache["latent"][:, 0]).max()) == 0  # the other slot was never touched


def test_chunked_prefill_is_the_whole_and_padding_and_a_used_slot_change_nothing(model):
    ids, ref = model["ids"], model["reference"]
    cache = decoder.init_cache(model["cfg"], 2, POSITIONS)
    # the slot has held another, longer sequence before
    _, cache, _ = _prefill(model, cache, 0, 0, np.arange(1000, 1040), 40)
    # three chunks of one bucket, the last padded; the padding's rows are written past the prompt and never read
    for start in (0, 16, 32):
        chunk = ids[start : min(start + 16, 37)]
        logits, cache, _ = _prefill(model, cache, 0, start, chunk, 16)
        assert np.abs(np.asarray(logits) - ref[start + len(chunk) - 1]).max() < TOLERANCE
    for t in range(37, 41):  # decode overwrites the padding's rows one by one
        logits, cache, _ = _decode(model, cache, 0, t, ids[t])
        assert np.abs(np.asarray(logits) - ref[t]).max() < TOLERANCE


def test_the_absorbed_form_is_the_expanded_form(model):
    """Token t through the decode program (the query carried into the latent
    space) and through the prefill program as a chunk of one (keys and values
    expanded per head) give the same logits."""
    ids = model["ids"]
    cache = decoder.init_cache(model["cfg"], 1, POSITIONS)
    _, cache, _ = _prefill(model, cache, 0, 0, ids[:32], 32)
    absorbed, _, _ = _decode(model, jax.tree.map(jnp.copy, cache), 0, 32, ids[32])
    chunk = np.zeros(8, np.int32)
    chunk[0] = ids[32]
    expanded, _, _ = model["prefill"](model["params"], jnp.asarray(chunk), cache, 0, 32, 1, config=model["cfg"])
    assert np.abs(np.asarray(absorbed) - np.asarray(expanded)).max() < TOLERANCE


def test_what_the_reference_would_miss_if_it_were_blind_to_the_layer(model):
    """Each piece of section 1 moves the reference's logits by far more than
    the tolerance: where the branch returns, the scale of the latent rows,
    the identity term, and the gates' not being renormalised."""
    ids, ref, params = model["ids"], model["reference"], model["params"]
    at = [[43]]
    unscaled = family.reference_logits(params, dict(GROUP, mla_scale_kv_lora=False), [ids], at, q_block=16)[0]
    assert np.abs(unscaled - ref[43]).max() > 1e-2
    # a layer whose routed branch is dropped (no expert held, and none of the router's 24 outputs past the 64 "routed" ones)
    dropped = family.reference_logits(params, dict(GROUP, n_routed_experts=0, n_routed_experts_published=64), [ids], at, q_block=16)[0]
    assert np.abs(dropped - ref[43]).max() > 1e-2
    # the program with the branch joined after the first dense block, not the second, is another model
    cfg = model["cfg"]
    h = jnp.asarray(np.random.default_rng(5).normal(0, 1, (8, 64)), jnp.float32)
    lp, live = params["layers"][0], jnp.ones((8,), bool)
    quiet = lambda i, h: jnp.tanh(h) * (i + 1)
    whole, _, _ = decoder._layer(h, lp, None, 0, lambda h, ap, cache, sublayer: (quiet(sublayer, h), cache), live, cfg)
    a0 = h + quiet(0, h)
    x0 = mla_decoder._rms(a0, lp["mlp_norm"][0], cfg.rms_norm_eps)
    branch, _ = decoder._moe(x0, lp, live, cfg)
    early = a0 + mla_decoder._swiglu(x0, lp["mlp"][0], jnp.float32) + branch  # joined early: the second attention would see it
    a1 = early + quiet(1, early)
    x1 = mla_decoder._rms(a1, lp["mlp_norm"][1], cfg.rms_norm_eps)
    assert np.abs(np.asarray(a1 + mla_decoder._swiglu(x1, lp["mlp"][1], jnp.float32)) - np.asarray(whole)).max() > 1e-2


def test_the_shares_add_up(model):
    """Over the four shares of a layer's 16 routed experts: the routed parts
    that each share's ``_experts_here`` gives, plus the identity term and the
    dense path counted once, are the uncut reference's layer."""
    params, cfg = model["params"], model["cfg"]
    lp = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(3).normal(0, 1, (24, GROUP["hidden_size"])), jnp.float32)
    live = jnp.ones((24,), bool)
    still = lambda h, ap, cache, sublayer: (jnp.zeros_like(h), cache)  # no attention: the layer's feed-forward half alone
    x0, dense0 = family._dense(h, lp["mlp_norm"][0], lp["mlp"][0], eps=1e-5, precision="f32")
    routed, identity, chosen = family.moe(x0, lp, GROUP)
    x1, dense1 = family._dense(h + dense0, lp["mlp_norm"][1], lp["mlp"][1], eps=1e-5, precision="f32")
    uncut = np.asarray(h + dense0 + dense1 + routed + identity)
    whole, _, counted = decoder._layer(h, lp, None, 0, still, live, cfg)
    zero = int(np.sum(np.asarray(chosen) >= 16))
    assert np.abs(np.asarray(whole) - uncut).max() < TOLERANCE and 0 < zero < 24 * 4
    assert list(np.asarray(counted)) == [24 * 4 - zero, 24 * 4, zero, 1]  # 96 pairs fill more tiles of 4 than 16 experts: grouped
    dense_path = np.asarray(h + dense0 + dense1)  # what every chip computes alike
    total, pairs = np.zeros_like(uncut), 0
    for share in range(4):
        group = dict(GROUP, n_routed_experts=4, expert_offset=4 * share)
        drawn = family.make_params(group, 7)["layers"][1]["experts"]
        mine = jax.tree.map(lambda w: w[4 * share : 4 * share + 4], lp["experts"])
        # another offset draws another share of the same experts
        assert all(np.array_equal(np.asarray(a, np.float32), np.asarray(b)) for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(mine)))
        part, _, counted = decoder._layer(h, dict(lp, experts=mine), None, 0, still, live, config_of(group))
        reference_part, reference_identity, _ = family.moe(x0, dict(lp, experts=mine), group)
        assert np.abs(np.asarray(part) - dense_path - np.asarray(reference_part + reference_identity)).max() < TOLERANCE
        assert int(counted[1]) == 24 * 4 and int(counted[2]) == zero  # every share sees the whole router
        total += np.asarray(part) - dense_path - np.asarray(identity)  # this share's routed part alone
        pairs += int(counted[0])
    assert pairs == 24 * 4 - zero
    assert np.abs(total + dense_path + np.asarray(identity) - uncut).max() < 5e-5


def test_zero_computation_experts_alone_return_the_gated_input(model):
    """A bias that forces every choice past the routed experts: the branch is
    ``6 * sum(s) * x_0`` and every routed pair is a zero-computation one."""
    cfg, lp = model["cfg"], model["params"]["layers"][0]
    bias = jnp.where(jnp.arange(24) >= 16, 10.0, 0.0)
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1, (10, 64)), jnp.float32)
    live = jnp.arange(10) < 7
    branch, counted = decoder._moe(x, dict(lp, router_bias=bias), live, cfg)
    s = jax.nn.softmax(jnp.einsum("tc,ce->te", x, lp["router"], precision=jax.lax.Precision.HIGHEST), axis=-1)
    gated = 6.0 * jnp.sum(jax.lax.top_k(s[:, 16:], 4)[0], axis=1, keepdims=True) * x
    assert np.abs(np.asarray(branch) - np.asarray(gated)).max() < 1e-6
    assert list(np.asarray(counted)) == [0, 7 * 4, 7 * 4, 1]  # live rows only; the grouped product, over no pair here
    # the gates are the scores times the factor, not renormalised: they do not add up to it
    _chosen, gates = decoder._route(x, lp, cfg)
    assert float(jnp.abs(jnp.sum(gates, axis=1) - 6.0).min()) > 0.5


def test_the_vocabulary_slice(model):
    """A model that holds the first rows of the vocabulary gives the uncut
    model's logits over those rows."""
    held = 1024
    group = dict(GROUP, vocab_size=held)
    drawn = family.make_params(group, 7)
    assert np.array_equal(np.asarray(drawn["embed"]), np.asarray(family.make_params(GROUP, 7)["embed"][:held]))
    params = dict(model["params"], embed=model["params"]["embed"][:held], head=model["params"]["head"][:, :held])
    ids = np.arange(1000, 1020).astype(np.int32)
    cfg = config_of(group)
    sliced, _, _ = _prefill(model, decoder.init_cache(cfg, 1, POSITIONS), 0, 0, ids, 24, cfg=cfg, params=params)
    whole, _, _ = _prefill(model, decoder.init_cache(model["cfg"], 1, POSITIONS), 0, 0, ids, 24)
    assert sliced.shape == (held,) and np.abs(np.asarray(sliced) - np.asarray(whole)[:held]).max() < 1e-6


def test_bfloat16_stays_near_the_reference_and_the_fp8_control_does_not(model):
    """The serving type: bfloat16 weights and caches, float32 accumulation.
    The tolerance is a quarter of the logits' spread at the worst of the
    vocabulary's rows (bfloat16 keeps 8 bits of every product's inputs through
    two double layers); the control, the reference with every product's
    inputs in float8_e4m3fn (4 bits), lies outside it."""
    cfg = dataclasses.replace(model["cfg"], dtype=jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, model["params"])
    ids = model["ids"]
    ref = family.reference_logits(params, GROUP, [ids], [[23, 24]], q_block=16)[0]
    cache = decoder.init_cache(cfg, 1, POSITIONS)
    assert cache["latent"].dtype == jnp.bfloat16
    logits, cache, _ = model["prefill"](params, jnp.asarray(ids[:24]), cache, 0, 0, 24, config=cfg)
    assert np.abs(np.asarray(logits) - ref[0]).max() < 0.25 * ref.std()
    logits, _, _ = model["decode"](params, jnp.asarray(ids[24:25]), cache, jnp.asarray([0]), jnp.asarray([24]), config=cfg)
    assert np.abs(np.asarray(logits[0]) - ref[1]).max() < 0.25 * ref.std()
    control = family.reference_logits(params, GROUP, [ids], [[23, 24]], precision="fp8", q_block=16)[0]
    assert np.abs(control - ref).max() > 0.25 * ref.std()


def _branch_dropped(x, lp, live, cfg, _moe=decoder._moe):
    branch, counted = _moe(x, lp, live, cfg)
    return jnp.zeros_like(branch), counted


def _identity_dropped(x, lp, cfg, _route=decoder._route):
    chosen, gates = _route(x, lp, cfg)
    return chosen, jnp.where(chosen >= cfg.n_routed_experts, 0.0, gates)


def _mask_lagged(q_nope, q_rope, rows, mask, n_blocks, lp, cfg, start=None, length=None, _core=decoder._prefill_core):
    newest = jnp.maximum(jnp.sum(mask, axis=1) - 1 - cfg.key_block, 0)  # every query loses its newest key block; key 0 stays
    return _core(q_nope, q_rope, rows, jnp.arange(mask.shape[1])[None, :] <= newest[:, None], n_blocks, lp, cfg, start, length)


def _caches_crossed(cache, sublayer, slot, _rows_of=decoder._rows_of):
    return _rows_of(cache, sublayer & ~1, slot)  # a layer's second attention reads the first's rows


@pytest.mark.parametrize(
    "name, fault",
    [("_moe", _branch_dropped), ("_route", _identity_dropped), ("_prefill_core", _mask_lagged), ("_rows_of", _caches_crossed)],
    ids=["the_branch_never_returns", "no_identity_term", "the_mask_lags_a_key_block", "the_second_attention_reads_the_firsts_cache"],
)
def test_a_fault_planted_in_the_program_lies_outside_the_serving_tolerance(model, monkeypatch, name, fault):
    """What the architecture adds is held by the comparison, not only the
    dense path: with one piece of the program replaced by a plausible mistake,
    the float32 program leaves the reference by more than the quarter of the
    logits' spread that the bfloat16 program is allowed (the chip's check was
    fitted the same way, against these faults at the published widths:
    ``benchmark/workloads/longcat-flash-chat-ep32.answer.json`` ``limits_from``)."""
    monkeypatch.setattr(decoder, name, fault)
    cfg, ids, ref = model["cfg"], model["ids"], model["reference"]
    prefill = jax.jit(lambda *a, **k: decoder.prefill(*a, **k), static_argnames=("config",))  # traced anew, over the fault
    decode = jax.jit(lambda *a, **k: decoder.decode_step(*a, **k), static_argnames=("config",))
    logits, cache, _ = prefill(model["params"], jnp.asarray(ids[:24]), decoder.init_cache(cfg, 1, POSITIONS), 0, 0, 24, config=cfg)
    step, _, _ = decode(model["params"], jnp.asarray(ids[24:25]), cache, jnp.asarray([0]), jnp.asarray([24]), config=cfg)
    gaps = [np.abs(np.asarray(logits) - ref[23]).max(), np.abs(np.asarray(step[0]) - ref[24]).max()]
    assert min(gaps) > 0.25 * ref[23:25].std(), gaps


# ------------------------------------------------------------ the executor
def _preset_names():
    from pathway_tpu.xpacks.llm import llms

    return sorted(llms._DECODER_PRESETS)


@pytest.mark.parametrize("name", _preset_names())
def test_every_decoder_preset_gives_the_executor_what_it_takes(name):
    """The seam is a convention: ``JittedDecoder`` finds five names in the
    module of the configuration's class and ``generate`` bumps the module's
    ``STATS`` as counters.  Every preset's module has them, with counters
    ``device_counters.bump`` knows and the fields the executor and the chat read."""
    import inspect

    from pathway_tpu.xpacks.llm.llms import decoder_preset

    config = decoder_preset(name)
    arch = __import__(type(config).__module__, fromlist=["_"])
    assert dataclasses.is_dataclass(config) and config.key_block > 0 and 0 < config.vocab_held <= config.vocab_size
    assert isinstance(arch.DISPATCH_TOKENS, int) and set(arch.STATS) <= set(devctr.snapshot())
    assert list(inspect.signature(arch.init_cache).parameters) == ["config", "slots", "positions"]
    assert list(inspect.signature(arch.prefill).parameters) == ["params", "ids", "cache", "slot", "start", "length", "last", "config"]
    assert list(inspect.signature(arch.decode_step).parameters) == ["params", "ids", "cache", "slots", "lengths", "config"]


def test_a_share_of_the_experts_counts_the_rows_it_computed_and_is_the_references_share(model):
    group = dict(GROUP, n_routed_experts=4, expert_offset=4)
    params = dict(model["params"], layers=[dict(lp, experts=jax.tree.map(lambda w: w[4:8], lp["experts"])) for lp in model["params"]["layers"]])
    share = JittedDecoder(config_of(group), params=params, slots=1, positions=POSITIONS, chunk_buckets=(8, 16))
    before = devctr.snapshot()
    out = share.generate(model["ids"][:30], 2)
    moved = {k: v - before.get(k, 0) for k, v in devctr.snapshot().items()}
    assert moved["moe_rows_routed"] == 2 * 4 * 31 and 0 < moved["moe_rows_here"] < moved["moe_rows_routed"] - moved["moe_rows_zero"]
    whole = np.concatenate([model["ids"][:30], out["ids"]])
    ref = family.reference_logits(params, group, [whole], [[29, 30]], q_block=16)[0]
    assert np.abs(out["logits"] - ref).max() < TOLERANCE


# ------------------------------------------- the core two architectures share
def _prefill_attention_before_the_split(q_nope, q_rope, qi, wi, latent_rows, index_rows, pos, n_blocks, lp, cfg, start, length):
    """``models/decoder.py``'s ``_prefill_attention`` as it stood before its
    core was split out (its ``jax.numpy`` branch, the one a CPU lowers; the
    chunk's ``start`` and real rows, which only the kernel's schedule reads,
    came in later)."""
    _mm, _NEG = mla_decoder._mm, mla_decoder._NEG
    C, KB, dt = q_nope.shape[0], cfg.key_block, cfg.dtype
    H, nope, vd, rank = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    L = latent_rows.shape[0]

    def score_block(b, scores):
        keys = jax.lax.dynamic_slice_in_dim(index_rows, b * KB, KB)
        per_head = jax.nn.relu(_mm("tjd,sd->tjs", qi, keys))
        return jax.lax.dynamic_update_slice_in_dim(scores, jnp.sum(per_head * wi[:, :, None], axis=1), b * KB, axis=1)

    scores = jax.lax.fori_loop(0, n_blocks, score_block, jnp.full((C, L), _NEG, jnp.float32))
    visible = jnp.arange(L)[None, :] <= pos[:, None]
    selected = mla_decoder._select(scores, visible, cfg.index_topk)

    def attend_block(b, carry):
        top, mass, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(latent_rows, b * KB, KB)
        kv = _mm("sr,rd->sd", rows[:, :rank], lp["kv_b"], dt).reshape(KB, H, nope + vd)
        s = (_mm("thd,shd->hts", q_nope, kv[..., :nope]) + _mm("thd,sd->hts", q_rope, rows[:, rank:])) * cfg.softmax_scale
        sel = jax.lax.dynamic_slice_in_dim(selected, b * KB, KB, axis=1)[None]
        new_top = jnp.maximum(top, jnp.max(jnp.where(sel, s, _NEG), axis=-1))
        p = jnp.where(sel, jnp.exp(s - new_top[..., None]), 0.0)
        shrink = jnp.exp(top - new_top)
        acc = acc * shrink[..., None] + _mm("hts,shd->htd", p.astype(dt), kv[..., nope:])
        return new_top, mass * shrink + jnp.sum(p, axis=-1), acc

    start = (jnp.full((H, C), _NEG, jnp.float32), jnp.zeros((H, C), jnp.float32), jnp.zeros((H, C, vd), jnp.float32))
    _, mass, acc = jax.lax.fori_loop(0, n_blocks, attend_block, start)
    out = (acc / mass[..., None]).astype(dt).transpose(1, 0, 2).reshape(C, H * vd)
    return _mm("td,dc->tc", out, lp["o"]), selected, visible


def _decode_attention_before_the_split(q_nope, q_rope, qi, wi, latent_rows, index_rows, pos, lp, cfg):
    _mm, _NEG = mla_decoder._mm, mla_decoder._NEG
    dt, rank, nope = cfg.dtype, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    H, L = cfg.num_attention_heads, latent_rows.shape[0]
    kv_b = lp["kv_b"].reshape(rank, H, nope + cfg.v_head_dim)
    index = jnp.sum(jax.nn.relu(_mm("jd,sd->js", qi, index_rows)) * wi[:, None], axis=0)
    visible = (jnp.arange(L) <= pos)[None, :]
    selected = mla_decoder._select(index[None, :], visible, cfg.index_topk)
    q_latent = _mm("hd,rhd->hr", q_nope, kv_b[..., :nope], dt)
    s = (_mm("hr,sr->hs", q_latent, latent_rows[:, :rank]) + _mm("hd,sd->hs", q_rope, latent_rows[:, rank:])) * cfg.softmax_scale
    p = jax.nn.softmax(jnp.where(selected, s, _NEG), axis=-1)
    mixed = _mm("hs,sr->hr", p.astype(dt), latent_rows[:, :rank], dt)
    out = _mm("hr,rhd->hd", mixed, kv_b[..., nope:], dt).reshape(-1)
    return out, jnp.sum(selected).astype(jnp.int32), jnp.sum(visible).astype(jnp.int32)


def test_the_other_architectures_programs_are_what_they_were_before_the_core_was_split_out(monkeypatch):
    """``models/decoder.py``'s two programs with its attention functions as
    they stood before ``_prefill_core`` / ``_decode_core`` were split out of
    them, against the programs as they are: the prefill program's lowered text
    is the same text; the decode program's differs in where one reshape of
    ``W_kvb`` stands (before the indexer's scores then, after the selection
    now), so it is held by its outputs, bit for bit."""
    from tests.test_decoder import GROUP as DEEPSEEK, config_of as deepseek_config, float32_params as deepseek_params

    cfg, params = deepseek_config(DEEPSEEK), deepseek_params(DEEPSEEK)
    ids = np.random.default_rng(0).integers(1000, DEEPSEEK["vocab_size"], size=25).astype(np.int32)
    one = jnp.asarray([1])

    def programs():
        prefill = jax.jit(lambda p, i, c, s, st, n: mla_decoder.prefill(p, i, c, s, st, n, config=cfg))
        decode = jax.jit(lambda p, i, c, s, n: mla_decoder.decode_step(p, i, c, s, n, config=cfg))
        cache = mla_decoder.init_cache(cfg, 2, 48)
        text = prefill.lower(params, jnp.asarray(ids[:24]), cache, 1, 0, 24).as_text()
        logits, cache, stats = prefill(params, jnp.asarray(ids[:24]), cache, 1, 0, 24)
        step, cache, step_stats = decode(params, jnp.asarray(ids[24:]), cache, one, jnp.asarray([24]))
        return text, [np.asarray(a) for a in (logits, stats, step, step_stats, cache["latent"], cache["index_k"])]

    text, outputs = programs()
    monkeypatch.setattr(mla_decoder, "_prefill_attention", _prefill_attention_before_the_split)
    monkeypatch.setattr(mla_decoder, "_decode_attention", _decode_attention_before_the_split)
    text_before, outputs_before = programs()
    assert text == text_before and "stablehlo.while" in text
    assert all(np.array_equal(a, b) for a, b in zip(outputs, outputs_before))
    ref = deepseek_family.reference_logits(params, DEEPSEEK, [ids], [[23, 24]], q_block=16)[0]
    assert np.abs(outputs[0] - ref[0]).max() < TOLERANCE and np.abs(outputs[2][0] - ref[1]).max() < TOLERANCE


def test_the_core_with_the_causal_mask_is_the_plain_softmax_over_every_visible_key(model):
    """``_prefill_core`` and ``_decode_core`` as this architecture calls
    them, against scores, softmax and weighted sum written out per head."""
    cfg, ap = model["cfg"], model["params"]["layers"][0]["attn"][1]
    rng = np.random.default_rng(2)
    L, C, start = 32, 8, 16
    H, nope, rope, vd, rank = 4, 16, 8, 16, 16
    rows = jnp.asarray(rng.normal(0, 1, (L, rank + rope)), jnp.float32)
    q_nope, q_rope = jnp.asarray(rng.normal(0, 1, (C, H, nope)), jnp.float32), jnp.asarray(rng.normal(0, 1, (C, H, rope)), jnp.float32)
    visible = jnp.arange(L)[None, :] <= (start + jnp.arange(C))[:, None]
    kv = (rows[:, :rank] @ ap["kv_b"]).reshape(L, H, nope + vd)
    scores = (jnp.einsum("thd,shd->hts", q_nope, kv[..., :nope]) + jnp.einsum("thd,sd->hts", q_rope, rows[:, rank:])) / np.sqrt(nope + rope)
    plain = jnp.einsum("hts,shd->thd", jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1), kv[..., nope:]).reshape(C, H * vd)
    got = mla_decoder._prefill_core(q_nope, q_rope, rows, visible, (start + C + 7) // 8, ap, cfg)
    assert np.abs(np.asarray(got) - np.asarray(plain @ ap["o"])).max() < 1e-5
    last = mla_decoder._decode_core(q_nope[-1], q_rope[-1], rows, visible[-1:], ap, cfg)
    assert np.abs(np.asarray(last) - np.asarray(plain[-1])).max() < 1e-5


def test_the_prefill_through_the_fused_kernel_is_the_jax_numpy_branch_and_counts_its_tiles(model, monkeypatch):
    """``_prefill_core``'s TPU branch, the fused kernel in interpret mode, over
    a prompt of 21 tokens in a chunk of 16 and one of 24 that holds 5 (query
    tiles of a key block's 8 rows: the last chunk's second and third are
    padding, so the kernel writes their rows zero), then two decode steps:
    the logits are the ``jax.numpy`` branch's, every row of the latent caches
    is finite (a padding row's output reaches them and, through decode's
    weighted sum, every later token), and ``mla_keys_multiplied`` is what the
    schedule multiplies (the experts' kernel, ``ops/grouped_experts.py``, in
    interpret mode too)."""
    from pathway_tpu.ops import grouped_experts as experts_kernel
    from pathway_tpu.ops import selected_attention as kernel

    cfg, params, ids = model["cfg"], model["params"], model["ids"]
    multiplied = decoder.STATS.index("mla_keys_multiplied")

    def generation():
        prefill = jax.jit(lambda *a, **k: decoder.prefill(*a, **k), static_argnames=("config",))  # traced anew, on either branch
        cache = decoder.init_cache(cfg, 1, POSITIONS)
        first, cache, counted_first = prefill(params, jnp.asarray(ids[:16]), cache, 0, 0, 16, config=cfg)
        second, cache, counted_second = prefill(params, jnp.asarray(np.pad(ids[16:21], (0, 19))), cache, 0, 16, 5, config=cfg)
        steps = []
        for t in (21, 22):
            step, cache, _ = model["decode"](params, jnp.asarray(ids[t : t + 1]), cache, jnp.asarray([0]), jnp.asarray([t]), config=cfg)
            steps.append(step[0])
        return [np.asarray(a) for a in (first, second, *steps)], cache, [int(counted_first[multiplied]), int(counted_second[multiplied])]

    plain, _, _ = generation()
    traced = []

    def interpreted(*args, _kernel=kernel.selected_attention, **kwargs):
        traced.append(args[0].shape)
        return _kernel(*args, **kwargs, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "selected_attention", interpreted)
    monkeypatch.setattr(experts_kernel, "grouped_experts", lambda *a, _k=experts_kernel.grouped_experts, **kw: _k(*a, **kw, interpret=True))
    fused, cache, counted = generation()
    assert traced == [(4, 16, 16)] * 4 + [(4, 24, 16)] * 4  # two chunks through four sublayers
    assert all(np.abs(a - b).max() < TOLERANCE for a, b in zip(fused, plain))
    assert np.isfinite(np.asarray(cache["latent"])).all()
    tiles = [kernel.query_tiles(0, 16, 16, block_k=8), kernel.query_tiles(16, 5, 24, block_k=8)]
    assert [np.asarray(v).tolist() for _, v in tiles] == [[1, 2], [3, 0, 0]]
    assert counted == [4 * rows * 8 * int(np.sum(v)) for rows, v in tiles] == [4 * 8 * 8 * 3, 4 * 8 * 8 * 3]

"""The generation stage's fourth architecture at a toy size on the CPU: the
decoder of grouped-query attention with window and global layers, a router
that reads the layer's input and ReGLU experts
(``models/window_moe_decoder.py``), held against the plain reference of the
benchmark's ``smallthinker`` family (float32 ``jax.numpy``, the whole
sequence at once, no cache, ring, chunks or kernel), on seeded weights; the
expert loop it shares with ``models/decoder.py`` (an activation of its own,
SwiGLU left the default); and the fused kernel's grouped, windowed form in
interpret mode (``JittedDecoder``, ``TPUDecoderChat`` and the answer route run
over every architecture in ``test_decoder.py``: ``served``; the kernel's
compile for a v5e at the published widths stands there too: one file loads
libtpu)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import smallthinker as family
from pathway_tpu.models import decoder as mla_decoder
from pathway_tpu.models import window_moe_decoder as decoder
from tests.window_moe_toy import GROUP, POSITIONS, config_of, float32_params

#: float32 program against float32 reference: what is left is the order of summation and where the softmax scale is
#: applied (measured 2.3e-6 on logits of unit spread)
TOLERANCE = 2e-5
#: a fault is a different model: it moves some logit by a tenth of the logits' spread or more (the faults below: 1.16-3.2)
SEEN = 0.1


@pytest.fixture(scope="module")
def model():
    cfg = config_of(GROUP)
    params = float32_params(GROUP)
    ids = np.random.default_rng(0).integers(1000, GROUP["vocab_size"], size=46).astype(np.int32)
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


def _generation(model, plan, decode_to, cfg=None, params=None, slot=0, cache=None):
    """The prompt of ``model["ids"]`` in the chunks ``plan`` ((start, real,
    bucket) each), then decode steps up to position ``decode_to``: the logits
    at each chunk's last real token and at each decoded position."""
    cfg = cfg or model["cfg"]
    cache = decoder.init_cache(cfg, 2, POSITIONS) if cache is None else cache
    out = {}
    for start, real, bucket in plan:
        logits, cache, _ = _prefill(model, cache, slot, start, model["ids"][start : start + real], bucket, cfg, params)
        out[start + real - 1] = np.asarray(logits)
    for t in range(plan[-1][0] + plan[-1][1], decode_to):
        logits, cache, _ = _decode(model, cache, slot, t, model["ids"][t], cfg, params)
        out[t] = np.asarray(logits)
    return out, cache


#: a prompt of 37 tokens in chunks that start inside a window (24 = 16 + 8) and whose last one is padded, then decode
#: steps past the window twice over, so that the ring wraps in the prefill and again in decode
PLAN = [(0, 16, 16), (16, 8, 8), (24, 13, 16)]


def _far(got: dict, ref) -> float:
    return max(float(np.abs(v - ref[t]).max()) for t, v in got.items())


def test_the_built_configuration_is_the_groups_and_the_preset_is_the_published_one(model):
    from pathway_tpu.xpacks.llm.llms import decoder_preset

    assert family.built_differs(GROUP, model["cfg"]) == {}
    assert family.built_differs(GROUP, dataclasses.replace(model["cfg"], sliding_window_size=8)) == {"sliding_window_size": (8, 16)}
    published = decoder_preset("PowerInfer/SmallThinker-21BA3B-Instruct")
    assert published is decoder.SMALLTHINKER_21BA3B is decoder_preset("smallthinker-21ba3b-instruct")
    assert (published.num_hidden_layers, published.num_attention_heads, published.num_key_value_heads, published.head_dim) == (52, 28, 4, 128)
    assert (published.moe_num_primary_experts, published.moe_num_active_primary_experts, published.moe_ffn_hidden_size) == (64, 6, 768)
    assert (published.sliding_window_size, published.vocab_held, published.rope_theta, published.rms_norm_eps) == (4096, 151936, 1.5e6, 1e-6)
    assert published.kinds[:5] == ((False, 0), (True, 0), (True, 1), (True, 2), (False, 1)) and sum(w for w, _ in published.kinds) == 39
    with pytest.raises(ValueError, match="renormalised"):
        dataclasses.replace(published, norm_topk_prob=False)
    with pytest.raises(ValueError, match="layouts"):
        dataclasses.replace(published, rope_layout=(0, 1, 1, 1))


def test_prefill_then_decode_through_cache_and_ring_is_the_references_full_forward(model):
    cache = decoder.init_cache(model["cfg"], 2, POSITIONS)
    # one global layer by position, three window layers in rings of 16, two slots, two K/V heads
    assert {k: v.shape for k, v in cache.items()} == {"k": (1, 2, 2, 48, 16), "v": (1, 2, 2, 48, 16), "ring_k": (3, 2, 2, 16, 16), "ring_v": (3, 2, 2, 16, 16)}
    got, cache = _generation(model, [(0, 24, 24)], 46, slot=1, cache=cache)
    assert _far(got, model["reference"]) < TOLERANCE
    assert all(float(jnp.abs(a[:, 0]).max()) == 0 for a in cache.values())  # the other slot was never touched


def test_chunks_that_start_inside_a_window_pad_and_wrap_the_ring_and_a_used_slot_change_nothing(model):
    cache = decoder.init_cache(model["cfg"], 1, POSITIONS)
    other = np.arange(1000, 1046).astype(np.int32)  # the slot has held another, longer sequence before
    _, cache, _ = _prefill(model, cache, 0, 0, other[:40], 40)
    got, _ = _generation(model, PLAN, 46, cache=cache)
    assert sorted(got)[:3] == [15, 23, 36] and _far(got, model["reference"]) < TOLERANCE


def test_what_the_counters_count(model):
    """A chunk's window pairs are its live queries' keys inside the window
    and what the kernel's query tiles multiply (8 rows, blocks of 8 from the
    one each tile's window reaches); a decode step multiplies the ring; the
    expert product multiplies whole blocks of 4 rows an expert, through the
    grouped product in each layer of a chunk (32 and 16 pairs can fill 14 and
    10 tiles, more than the 8 experts) and the block loop in a decode step's
    (2 pairs)."""
    cache = decoder.init_cache(model["cfg"], 1, POSITIONS)
    _, cache, first = _prefill(model, cache, 0, 0, model["ids"][:16], 16)
    _, cache, second = _prefill(model, cache, 0, 16, model["ids"][16:21], 8)
    _, _, step = _decode(model, cache, 0, 21, model["ids"][21])
    names = {n: i for i, n in enumerate(decoder.STATS)}
    counts = [dict(zip(decoder.STATS, np.asarray(c).tolist())) for c in (first, second, step)]
    in_window = lambda positions: 3 * sum(min(t + 1, 16) for t in positions)
    assert [c["swa_keys_in_window"] for c in counts] == [in_window(range(16)), in_window(range(16, 21)), in_window([21])]
    # chunk at 0: tiles visit blocks 2-2 and 2-3 of ring + chunk (the ring's empty positions are skipped); chunk at 16: blocks 0-2
    assert [c["swa_keys_multiplied"] for c in counts] == [3 * 8 * 8 * 3, 3 * 8 * 8 * 3, 3 * 16]
    assert [c["moe_rows_routed"] for c in counts] == [4 * 2 * 16, 4 * 2 * 5, 4 * 2] == [c["moe_rows_here"] for c in counts]
    for c in counts:
        assert c["moe_rows_multiplied"] % 4 == 0 and c["moe_rows_here"] <= c["moe_rows_multiplied"] <= c["moe_rows_here"] + 4 * 8 * 3
    assert [c["moe_grouped_calls"] for c in counts] == [4, 4, 0]
    assert set(names) == {"moe_rows_here", "moe_rows_routed", "moe_rows_multiplied", "moe_grouped_calls", "swa_keys_in_window", "swa_keys_multiplied"}


def _per_token(x, chosen, gates, experts, activation, dt=jnp.float32):
    """The routed sum written out token by token."""
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for e, g in zip(np.asarray(chosen[t]), np.asarray(gates[t])):
            p = jax.tree.map(lambda w: w[int(e)], experts)
            out[t] += float(g) * np.asarray(activation(x[t : t + 1], p, dt))[0]
    return out


@pytest.mark.parametrize("path", ["grouped", "loop"])
@pytest.mark.parametrize("activation", ["reglu", "swiglu by default"])
def test_the_expert_loop_is_the_per_token_sum_with_either_activation(model, monkeypatch, activation, path):
    """12 tokens, 2 pairs each, can fill 12 tiles of 4 against 8 experts: the
    grouped product; the block loop where ``_grouped`` is told no."""
    cfg = model["cfg"]
    lp = model["params"]["layers"][2]
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (12, 64)), jnp.float32)
    chosen, gates = decoder._route(x, lp, cfg)
    live = jnp.ones((12,), bool)
    assert mla_decoder._grouped(chosen.size, lp["experts"], cfg)
    if path == "loop":
        monkeypatch.setattr(mla_decoder, "_grouped", lambda *a: False)
    if activation == "reglu":
        got, pairs = mla_decoder._experts_here(x, chosen, gates, live, lp["experts"], cfg, activation=decoder._reglu)
        want = _per_token(x, chosen, gates, lp["experts"], decoder._reglu)
        other = _per_token(x, chosen, gates, lp["experts"], mla_decoder._swiglu)
        assert np.abs(want - other).max() > SEEN  # the two units are told apart
    else:
        got, pairs = mla_decoder._experts_here(x, chosen, gates, live, lp["experts"], cfg)
        want = _per_token(x, chosen, gates, lp["experts"], mla_decoder._swiglu)
    assert int(pairs) == 24 and np.abs(np.asarray(got) - want).max() < 1e-5


#: the token-expert pairs of 16 tokens, 2 a token, the last two tokens padding: in blocks of 4, one held expert gets no
#: pair, one exactly a block and one more than two blocks.  "whole": the 8 experts held; "share": experts 4-7 held, ids
#: 0-3 another chip's and 9 past the router's 8 experts (a router wider than the experts that hold parameters, as
#: LongCat's, whose caller adds what those give)
PAIRS = {
    "whole": [(1, 2)] * 4 + [(2, 3)] * 5 + [(4, 5), (6, 7), (3, 4), (5, 6), (7, 3), (4, 6), (5, 7)],
    "share": [(5, 0)] * 4 + [(6, 9)] * 9 + [(7, 2)] * 3,
}


def _pairs_case(model, held: str):
    """The layer's experts held in ``held``'s share, its configuration, the rows, pairs, gates and live rows."""
    lp = model["params"]["layers"][2]
    offset, E = (0, 8) if held == "whole" else (4, 4)
    cfg = config_of(dict(GROUP, experts_held=E, expert_offset=offset))
    experts = jax.tree.map(lambda w: w[offset : offset + E], lp["experts"])
    chosen = jnp.asarray(PAIRS[held], jnp.int32)
    gates = jnp.asarray(np.random.default_rng(2).uniform(0.1, 1.0, chosen.shape), jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (16, 64)), jnp.float32)
    local = chosen - offset
    here = (local >= 0) & (local < E) & (jnp.arange(16) < 14)[:, None]
    return dict(lp, experts=experts), cfg, x, chosen, gates, jnp.arange(16) < 14, np.bincount(np.asarray(local)[np.asarray(here)], minlength=E)


@pytest.mark.parametrize("activation", ["reglu", "swiglu"])
@pytest.mark.parametrize("held", sorted(PAIRS))
def test_the_grouped_product_is_the_block_loop(model, monkeypatch, held, activation):
    """The grouped product (rows gathered once, each expert's tiles in one
    read, each token's pairs summed) gives the block loop's result to float32's
    order of summation, and the per-token sum, with the same pairs counted:
    over an expert held with no pair, one with exactly a block, one with more,
    pairs of another chip's experts and past the router's, and padding rows,
    which get nothing."""
    lp, cfg, x, chosen, gates, live, count = _pairs_case(model, held)
    act = {"reglu": decoder._reglu, "swiglu": mla_decoder._swiglu}[activation]
    assert count.min() == 0 and 4 in count and count.max() == 9
    assert mla_decoder._grouped(chosen.size, lp["experts"], cfg)
    grouped, pairs = mla_decoder._experts_here(x, chosen, gates, live, lp["experts"], cfg, activation=act)
    monkeypatch.setattr(mla_decoder, "_grouped", lambda *a: False)
    loop, loop_pairs = mla_decoder._experts_here(x, chosen, gates, live, lp["experts"], cfg, activation=act)
    local = chosen - cfg.expert_offset
    here = (local >= 0) & (local < cfg.experts_held) & live[:, None]
    want = _per_token(x, jnp.where(here, local, 0), jnp.where(here, gates, 0.0), lp["experts"], act)
    assert int(pairs) == int(loop_pairs) == int(count.sum()) == int(jnp.sum(here))
    assert np.abs(np.asarray(grouped) - np.asarray(loop)).max() < 1e-5 and np.abs(np.asarray(grouped) - want).max() < 1e-5
    assert float(jnp.abs(grouped[14:]).max()) == 0.0 and np.abs(want).max() > SEEN


@pytest.mark.parametrize("held", sorted(PAIRS))
def test_the_experts_kernel_multiplies_the_rows_the_counter_counts(model, monkeypatch, held):
    """``_experts_here``'s TPU branch, ``ops/grouped_experts.py`` in interpret
    mode: the kernel's grid computes its ``used`` tiles of 4 rows, expert by
    expert (each expert's tiles one run: one weight read), and repeats the
    last one's blocks past them; ``_moe``'s ``moe_rows_multiplied`` is those
    rows, so ``expert_rows_useful_pct`` reads what the grouped product
    multiplies; the result is the ``jax.numpy`` branch's."""
    from pathway_tpu.ops import grouped_experts as kernel

    lp, cfg, x, chosen, gates, live, count = _pairs_case(model, held)
    plain, plain_counted = decoder._moe(x, lp, chosen, gates, live, cfg)
    seen = []

    def interpreted(*args, _kernel=kernel.grouped_experts, **kwargs):
        seen.append((args[0].shape[0] // kwargs["block"], int(args[4][0]), np.asarray(args[2]).tolist(), np.asarray(args[3]).tolist()))
        return _kernel(*args, **kwargs, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "grouped_experts", interpreted)
    fused, counted = decoder._moe(x, lp, chosen, gates, live, cfg)
    (tiles, used, tile_expert, tile_at), = seen
    names = dict(zip(decoder.STATS, np.asarray(counted).tolist()))
    assert used == int(np.sum(-(-count // 4))) and names["moe_rows_multiplied"] == 4 * used and names["moe_grouped_calls"] == 1
    assert tiles == mla_decoder._tiles(chosen.size, cfg) > used
    assert tile_expert[:used] == sorted(np.repeat(np.arange(cfg.experts_held), -(-count // 4)).tolist())
    assert tile_at == list(range(used)) + [used - 1] * (tiles - used) and tile_expert[used:] == [tile_expert[used - 1]] * (tiles - used)
    assert np.asarray(counted).tolist() == np.asarray(plain_counted).tolist()
    assert np.abs(np.asarray(fused) - np.asarray(plain)).max() < 1e-5


def _experts_of(E: int, hidden: int, ffn: int):
    leaf = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16)
    return {"gate": leaf(E, hidden, ffn), "up": leaf(E, hidden, ffn), "down": leaf(E, ffn, hidden)}


@pytest.mark.parametrize(
    "cell, tokens, grouped",
    [
        ("smallthinker", 2560, True), ("smallthinker", 2048, True), ("smallthinker", 512, True),  # prompt chunks
        ("smallthinker", 8, False), ("smallthinker", 1, False),  # decode steps
        ("deepseek-ep16", 2560, False), ("deepseek-ep16", 8, False), ("longcat-ep32", 2560, False),  # experts too large
    ],
)
def test_the_path_follows_the_shapes(cell, tokens, grouped):
    """At the cells' published widths: SmallThinker's prompt chunks take the
    grouped product (6 pairs a token fill more tiles of 128 than its 64
    experts) and its decode steps (6-48 pairs) the loop; DeepSeek's and
    LongCat's 16 held experts of 88 and 75 MB cannot stay in the kernel's
    VMEM while the next is fetched, so their chunks keep the loop."""
    from pathway_tpu.models.shortcut_moe_decoder import ShortcutMoEDecoderConfig

    cfg, K, experts = {
        "smallthinker": (decoder.SMALLTHINKER_21BA3B, 6, _experts_of(64, 2560, 768)),
        "deepseek-ep16": (mla_decoder.DecoderConfig(experts_held=16), 8, _experts_of(16, 7168, 2048)),
        "longcat-ep32": (ShortcutMoEDecoderConfig(experts_held=16), 12, _experts_of(16, 6144, 2048)),
    }[cell]
    assert mla_decoder._grouped(tokens * K, experts, cfg) is grouped


def test_the_two_halves_of_the_experts_add_up_to_the_uncut_layer(model):
    """Experts 0-3 on one chip and 4-7 on another: the routed parts each
    share's ``_moe`` gives add up to the whole layer's, both shares see the
    whole router, and another offset draws another share of the same
    experts."""
    params, cfg = model["params"], model["cfg"]
    lp = params["layers"][1]
    a = jnp.asarray(np.random.default_rng(3).normal(0, 1, (24, 64)), jnp.float32)
    live = jnp.arange(24) < 21
    chosen, gates = decoder._route(a, lp, cfg)
    uncut, counted = decoder._moe(a, lp, chosen, gates, live, cfg)
    total, pairs = np.zeros(uncut.shape, np.float32), 0
    for offset in (0, 4):
        group = dict(GROUP, experts_held=4, expert_offset=offset)
        drawn = family.make_params(group, 7)["layers"][1]["experts"]
        mine = jax.tree.map(lambda w: w[offset : offset + 4], lp["experts"])
        assert all(np.array_equal(np.asarray(d, np.float32), np.asarray(m)) for d, m in zip(jax.tree.leaves(drawn), jax.tree.leaves(mine)))
        part, share_counted = decoder._moe(a, dict(lp, experts=mine), chosen, gates, live, config_of(group))
        assert int(share_counted[1]) == 21 * 2  # every share sees the whole router
        total += np.asarray(part)
        pairs += int(share_counted[0])
    assert pairs == int(counted[0]) == 21 * 2 and np.abs(total - np.asarray(uncut)).max() < TOLERANCE
    x = family._post_norm(a, lp["mlp_norm"], eps=1e-6)
    want = np.asarray(family.routed(x, chosen, jnp.where(live[:, None], gates, 0.0), lp, GROUP))
    assert np.abs(np.asarray(uncut) - want).max() < TOLERANCE


def _router_on_the_normed_input(h, lp, cfg, _route=decoder._route):
    return _route(mla_decoder._rms(h, lp["attn_norm"], cfg.rms_norm_eps), lp, cfg)


def _rope_everywhere(cfg):
    return dataclasses.replace(cfg, rope_layout=(1, 1, 1, 1))


def _rope_nowhere(cfg):
    return dataclasses.replace(cfg, rope_layout=(0, 0, 0, 0))


def _swiglu_experts(x, chosen, gates, live, experts, cfg, activation=None, _here=mla_decoder._experts_here):
    return _here(x, chosen, gates, live, experts, cfg)


def _ring_written_first(ring, new, start, length, W, _sound=decoder._ring_and_chunk):
    """The chunk reads the ring as it leaves it, not as it stood."""
    _, left = _sound(ring, new, start, length, W)
    return _sound(left, new, start, length, W)[0], left


#: faults planted in the program (the chip's check plants the same at the published widths: PERF.md section 6)
FAULTS = {
    "router_reads_rms_h": ("_route", _router_on_the_normed_input),
    "rope_in_the_global_layer": ("config", _rope_everywhere),
    "no_rope_in_the_window_layers": ("config", _rope_nowhere),
    "swiglu_for_reglu": ("_experts_here", _swiglu_experts),
    "ring_written_before_the_chunk_reads_it": ("_ring_and_chunk", _ring_written_first),
    "window_one_block_short": ("config", lambda cfg: dataclasses.replace(cfg, sliding_window_size=8)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_is_another_model(model, monkeypatch, fault):
    """The router reading ``RMS(h)`` where the layer's input is meant, rope in
    the global layer or in none, SwiGLU for ReGLU, a chunk that reads the ring
    as it leaves it, a window a key block short: each moves the logits far
    past the tolerance, so no reading can be swapped for another unseen (the
    sound program's distance is ``TOLERANCE``, the tests above)."""
    what, planted = FAULTS[fault]
    cfg = model["cfg"]
    if what == "config":
        cfg = planted(cfg)
    else:
        monkeypatch.setattr(decoder, what, planted)
    prefill = jax.jit(lambda *a, **k: decoder.prefill(*a, **k), static_argnames=("config",))  # traced anew, with the fault
    got, _ = _generation(dict(model, prefill=prefill), PLAN, 40, cfg=cfg)
    assert _far(got, model["reference"]) > SEEN


def test_bfloat16_stays_near_the_reference_and_the_fp8_control_does_not(model):
    """The serving type: bfloat16 weights and caches, float32 accumulation.
    The tolerance is a quarter of the logits' spread at the worst of the
    vocabulary's rows (bfloat16 keeps 8 bits of every product's inputs
    through four layers); the control, the reference with every product's
    inputs in float8_e4m3fn (4 bits), lies outside it."""
    cfg = dataclasses.replace(model["cfg"], dtype=jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, model["params"])
    ids = model["ids"]
    ref = family.reference_logits(params, GROUP, [ids], [[23, 24]], q_block=16)[0]
    cache = decoder.init_cache(cfg, 1, POSITIONS)
    assert cache["ring_k"].dtype == jnp.bfloat16
    logits, cache, _ = model["prefill"](params, jnp.asarray(ids[:24]), cache, 0, 0, 24, config=cfg)
    assert np.abs(np.asarray(logits) - ref[0]).max() < 0.25 * ref.std()
    logits, _, _ = model["decode"](params, jnp.asarray(ids[24:25]), cache, jnp.asarray([0]), jnp.asarray([24]), config=cfg)
    assert np.abs(np.asarray(logits[0]) - ref[1]).max() < 0.25 * ref.std()
    control = family.reference_logits(params, GROUP, [ids], [[23, 24]], precision="fp8", q_block=16)[0]
    assert np.abs(control - ref).max() > 0.25 * ref.std()


def test_the_prefill_through_the_fused_kernel_is_the_jax_numpy_branch(model, monkeypatch):
    """``_attend``'s TPU branch, the grouped kernel in interpret mode (and the
    experts' kernel, ``ops/grouped_experts.py``, so), over the
    plan's three chunks (one starts inside a window; the last holds padding,
    one of its tiles whole), then decode: the logits are the ``jax.numpy``
    branch's and every row of every cache is finite."""
    from pathway_tpu.ops import grouped_experts as experts_kernel
    from pathway_tpu.ops import selected_attention as kernel

    plain, _ = _generation(model, PLAN, 40)
    traced = []

    def interpreted(*args, _kernel=kernel.grouped_attention, **kwargs):
        traced.append((args[0].shape, args[1].shape, kwargs["window"]))
        return _kernel(*args, **kwargs, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "grouped_attention", interpreted)
    monkeypatch.setattr(experts_kernel, "grouped_experts", lambda *a, _k=experts_kernel.grouped_experts, **kw: _k(*a, **kw, interpret=True))
    prefill = jax.jit(lambda *a, **k: decoder.prefill(*a, **k), static_argnames=("config",))  # traced anew, on this branch
    fused, cache = _generation(dict(model, prefill=prefill), PLAN, 40)
    assert traced[:4] == [((4, 16, 16), (2, 48, 16), None)] + [((4, 16, 16), (2, 32, 16), 16)] * 3
    assert all(np.abs(fused[t] - plain[t]).max() < TOLERANCE for t in plain)
    assert all(np.isfinite(np.asarray(a)).all() for a in cache.values())


# --------------------------------------------------------------- the kernel
def _plain_grouped(q, k, v, visible):
    H, G = q.shape[0], k.shape[0]
    kh, vh = jnp.repeat(k, H // G, axis=0), jnp.repeat(v, H // G, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, kh, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(visible[None], s, -1e30), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), vh, preferred_element_type=jnp.float32)


@pytest.mark.parametrize(
    "heads, kv, chunk, window, seq_start, length",
    [(4, 2, 256, 512, 0, 256), (4, 2, 256, 512, 384, 256), (7, 1, 256, 512, 1000, 100), (4, 4, 128, None, 200, 128), (6, 2, 128, 256, 96, 1)],
    ids=["window_at_0", "window_mid_block", "window_padding_tiles", "global", "one_live_row"],
)
def test_the_grouped_kernel_is_the_plain_softmax_and_reads_only_its_window(heads, kv, chunk, window, seq_start, length):
    """The grouped, windowed schedule in interpret mode, laid out as the
    window layers lay it out (the ``window`` positions before the chunk, then
    the chunk) or as a global layer's cache by position: real rows are the
    plain softmax's over grouped K/V, a tile of padding is zero, and keys
    before the first block a tile's window reaches, or before the sequence,
    are never fetched."""
    from pathway_tpu.ops.selected_attention import grouped_attention, window_tiles

    rng = np.random.default_rng(seq_start + length)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
    bk, bq = 128, 64
    if window:
        L, start, first_key = window + chunk, window, max(window - seq_start, 0)
    else:
        L, start, first_key = 640, seq_start, 0
    i, t = np.arange(L)[None, :], np.arange(chunk)[:, None]
    visible = (i <= start + t) & (i >= first_key) & ((i > start + t - window) if window else True)
    q, k, v = 0.2 * draw(heads, chunk, 128), draw(kv, L, 128), draw(kv, L, 128)
    rows, first, ends = window_tiles(start, length, chunk, window, first_key, bq, bk)
    real = np.asarray(ends) > 0
    lowest = int(np.asarray(first)[real].min()) * bk
    k, v = k.at[:, :lowest].set(jnp.nan), v.at[:, :lowest].set(jnp.nan)  # never fetched
    got = np.asarray(grouped_attention(q, k, v, jnp.asarray(visible), jnp.int32(start), jnp.int32(first_key), jnp.int32(length), window=window, block_q=bq, block_k=bk, interpret=True), np.float32)
    want = np.asarray(_plain_grouped(q, jnp.nan_to_num(k), jnp.nan_to_num(v), jnp.asarray(visible)))
    padding = np.repeat(~real, rows)
    assert rows == bq and np.isfinite(got).all() and (got[:, padding] == 0).all()
    assert float(np.abs(got[:, ~padding] - want[:, ~padding]).max()) < 0.03
    if window:  # a tile visits about the window and a block, not the whole prefix
        assert int(np.max((np.asarray(ends) - np.asarray(first))[real])) <= (window + bq) // bk + 1


def test_window_tiles_without_a_window_are_query_tiles_and_the_cells_plan():
    """With no window and no lower bound every tile starts at the first block
    and ends where :func:`query_tiles` ends it (the latent cells' schedule is
    unchanged); at this cell's plan a window layer's 256-row tiles visit 9 or
    10 of the ring-and-chunk's key blocks of 512, and its useful share of the
    pairs multiplied is 84 % where the whole prefix would be a half."""
    from pathway_tpu.ops.selected_attention import query_tiles, window_tiles

    for C, start, length in [(2560, 0, 2560), (2048, 2560, 2048), (2048, 4608, 1727), (512, 8704, 300)]:
        rows, ends = query_tiles(start, length, C, 256, 512)
        rows_w, first, ends_w = window_tiles(start, length, C, None, 0, 256, 512)
        assert rows_w == rows and np.array_equal(np.asarray(ends_w), np.asarray(ends)) and not np.asarray(first).any()
    W, C, seq_start = 4096, 2560, 10240
    rows, first, ends = window_tiles(W, C, C, W, max(W - seq_start, 0), 256, 512)
    visits = np.asarray(ends) - np.asarray(first)
    assert set(visits.tolist()) <= {9, 10}
    useful = C * W / (rows * 512 * visits.sum())
    assert 0.8 < useful < 0.9 and W / (W + seq_start) < 0.3

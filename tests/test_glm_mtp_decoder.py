"""GLM-4.7-Flash's decoder at a toy size on the CPU: latent attention with no
indexer and plain rope, and the MTP layer drafting one token a step
(``models/decoder.py``: ``prefill_drafted``, ``verify_step``) through the
drafting loop of ``JittedDecoder.generate``, held against the plain
reference of the benchmark's ``glm4_moe_lite`` family (float32
``jax.numpy``, the whole sequence at once, no cache, chunks or absorbed
form), on seeded weights; and the DeepSeek programs, which share the module,
as they traced before the indexer became optional."""

from __future__ import annotations

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import glm4_moe_lite as family
from pathway_tpu.internals import device_counters as devctr
from pathway_tpu.models import decoder
from pathway_tpu.parallel import JittedDecoder

#: hidden 64, 4 heads of 24 + 8 (values 32), 1 dense + 2 routed layers, 16 experts with 4 a token in one group, one MTP layer
GROUP = {
    "family": "glm4_moe_lite", "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 16, "experts_held": 16, "expert_offset": 0, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1.8, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 32, "rope_theta": 10000.0, "rope_scaling": None,
    "rms_norm_eps": 1e-5, "vocab_size": 1280, "num_nextn_predict_layers": 1, "param_dtype": "float32",
}
POSITIONS = 48
#: float32 program against float32 reference: what is left is the order of summation and the absorbed form of the
#: decode (measured 2.3e-6 on the main logits and 1.4e-6 on the drafts', both of spread about 1)
TOLERANCE = 2e-5
#: a fault is another model: it moves some draft logit by a tenth of the draft row's spread or more
SEEN = 0.1


def config_of(group: dict, **over) -> decoder.DecoderConfig:
    fields = {f.name for f in dataclasses.fields(decoder.DecoderConfig)} & set(group)
    given = {k: group[k] for k in fields}
    return dataclasses.replace(
        decoder.GLM_4_7_FLASH, **given, vocab_held=group["vocab_size"], **{"dtype": jnp.float32, "key_block": 8, "expert_block": 4, **over}
    )


@pytest.fixture(scope="module")
def model():
    params = jax.tree.map(lambda a: a.astype(jnp.float32), family.make_params(GROUP, 7))
    prompt = np.random.default_rng(0).integers(1000, GROUP["vocab_size"], size=21).astype(np.int32)
    cfg = config_of(GROUP)
    plain_cfg = dataclasses.replace(cfg, num_nextn_predict_layers=0)
    plain_params = {k: v for k, v in params.items() if k != "mtp"}
    plain = JittedDecoder(plain_cfg, params=plain_params, slots=2, positions=POSITIONS, chunk_buckets=(8, 16)).generate(prompt, 8)
    return {"cfg": cfg, "params": params, "prompt": prompt, "plain": plain, "plain_cfg": plain_cfg, "plain_params": plain_params}


def _executor(model, **kw):
    return JittedDecoder(model["cfg"], params=model["params"], slots=2, positions=POSITIONS, chunk_buckets=(8, 16), **kw)


def _moved(before):
    return {k: v - before.get(k, 0) for k, v in devctr.snapshot().items()}


def test_the_preset_is_the_published_configuration_and_the_toy_is_the_groups(model):
    from pathway_tpu.xpacks.llm.llms import decoder_preset

    glm = decoder_preset("zai-org/GLM-4.7-Flash")
    assert glm is decoder.GLM_4_7_FLASH and decoder_preset("glm-4.7-flash") is glm
    published = {
        "hidden_size": 2048, "num_hidden_layers": 47, "first_k_dense_replace": 1, "intermediate_size": 10240, "moe_intermediate_size": 1536,
        "n_routed_experts": 64, "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1.8,
        "num_attention_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-5, "vocab_size": 154880, "num_nextn_predict_layers": 1,
    }
    assert {k: getattr(glm, k) for k in published} == published
    assert (glm.index_topk, glm.experts_held, glm.vocab_held, glm.softmax_scale) == (0, 64, 154880, 1 / 16)
    assert np.allclose(glm.inv_freq(), 1e6 ** (-np.arange(0, 64, 2) / 64), rtol=1e-12)
    assert family.built_differs(GROUP, model["cfg"]) == {}
    assert family.built_differs(GROUP, dataclasses.replace(model["cfg"], index_topk=8)) == {"index_topk": (8, 0)}
    cache = decoder.init_cache(model["cfg"], 2, POSITIONS)
    assert set(cache) == {"latent", "mtp"} and cache["mtp"].shape == (1, 2, POSITIONS, 24)


def _generate(model, new, executor=None):
    before = devctr.snapshot()
    out = (executor or _executor(model)).generate(model["prompt"], new)
    return out, _moved(before)


def test_prefill_and_drafting_decode_are_the_references_main_and_draft_logits(model):
    """A prompt of 21 (a chunk of 16, whose last MTP position takes the
    next chunk's first id, and one of 8), 8 new tokens: every kept row and
    every draft's kept logits against the reference's full forward and its
    MTP layer over its own final-normed rows."""
    out, moved = _generate(model, 8)
    prompt, drafts = model["prompt"], out["drafts"]
    assert list(drafts["positions"]) == list(range(20, 28))  # the prompt's last position, then one a token emitted
    seq = np.concatenate([prompt, out["ids"]])
    main, draft = family.reference_draft_logits(model["params"], GROUP, [seq], [list(range(20, 28))], [list(drafts["positions"])], q_block=16)
    assert np.abs(out["logits"] - main[0]).max() < TOLERANCE and np.array_equal(out["ids"], out["logits"].argmax(axis=1))
    ref = draft[0]
    assert all(np.array_equal(np.argsort(-ref[i], kind="stable")[:8], drafts["ids"][i]) for i in range(8))
    assert max(np.abs(drafts["logits"][i] - ref[i][drafts["ids"][i]]).max() for i in range(8)) < TOLERANCE
    assert np.abs(family.reference_logits(model["params"], GROUP, [seq], [list(range(20, 28))], q_block=16)[0] - main[0]).max() == 0
    steps = moved["gen_decode_steps"]
    assert moved["mtp_drafts"] == steps and steps + moved["mtp_drafts_accepted"] >= 7 and steps <= 7
    # 2 routed layers and the MTP layer, 4 pairs a live token each: the prompt's 21 and two a verify step
    assert moved["moe_rows_routed"] == moved["moe_rows_here"] == 3 * 4 * (21 + 2 * steps)
    assert moved["dsa_keys_scored"] == moved["dsa_keys_selected"] == 0  # no indexer


def test_one_chunk_and_two_fill_the_same_mtp_rows(model):
    """The MTP layer's cache after a prompt of 32 in one chunk and in two of
    16: the first chunk's last position takes the second's first id."""
    cfg, params = model["cfg"], model["params"]
    ids = np.random.default_rng(4).integers(1000, GROUP["vocab_size"], size=32).astype(np.int32)
    run = jax.jit(decoder.prefill_drafted, static_argnames=("config",))
    whole = run(params, jnp.asarray(ids), 0, decoder.init_cache(cfg, 1, POSITIONS), 0, 0, 32, True, config=cfg)
    cache = decoder.init_cache(cfg, 1, POSITIONS)
    _, _, _, cache, _ = run(params, jnp.asarray(ids[:16]), ids[16], cache, 0, 0, 16, False, config=cfg)
    halves = run(params, jnp.asarray(ids[16:]), 0, cache, 0, 16, 16, True, config=cfg)
    assert np.abs(np.asarray(whole[3]["mtp"]) - np.asarray(halves[3]["mtp"])).max() < TOLERANCE
    assert np.abs(np.asarray(whole[2][0]) - np.asarray(halves[2][0])).max() < TOLERANCE and np.array_equal(whole[2][1], halves[2][1])
    wrong = run(params, jnp.asarray(ids[:16]), ids[16] + 1, decoder.init_cache(cfg, 1, POSITIONS), 0, 0, 16, False, config=cfg)
    assert np.abs(np.asarray(wrong[3]["mtp"])[0, 0, 15] - np.asarray(whole[3]["mtp"])[0, 0, 15]).max() > SEEN


def _oracle(monkeypatch, table, wrong_at=lambda p: False):
    """The draft replaced by the plain run's token at the position it
    guesses (``table``: the prompt and the plain run's ids), a wrong one
    where ``wrong_at`` says so; programs traced after this see it."""
    table = jnp.asarray(np.concatenate([table, np.zeros(POSITIONS, np.int32)]))
    wrong = jnp.asarray([wrong_at(p) for p in range(table.size)])

    def guess(p):
        return jnp.where(wrong[p], (table[p] + 1) % GROUP["vocab_size"], table[p])[None]

    def prefill_drafted(params, ids, following, cache, slot, start, length, last, *, config, _real=decoder.prefill_drafted):
        token, logits, (values, top), cache, stats = _real(params, ids, following, cache, slot, start, length, last, config=config)
        return token, logits, (values, top.at[0].set(guess(start + length + 1)[0])), cache, stats

    def verify_step(params, token, draft, cache, slot, length, *, config, _real=decoder.verify_step):
        emitted, chosen, rows, draft_out, (length, token, _), cache, stats = _real(params, token, draft, cache, slot, length, config=config)
        return emitted, chosen, rows, draft_out, (length, token, guess(length + 1)), cache, stats

    monkeypatch.setattr(decoder, "prefill_drafted", prefill_drafted)
    monkeypatch.setattr(decoder, "verify_step", verify_step)


@pytest.mark.parametrize("drafts", ["seeded", "oracle", "mixed"])
def test_the_drafting_loop_emits_what_plain_greedy_decoding_does(model, monkeypatch, drafts):
    """At acceptance 0 (the seeded MTP layer), all (an oracle draft) and
    mixed (an oracle wrong at every third position: a kept draft moves two
    positions on, so one wrong at every other would keep every draft or
    none): the ids and rows of the same weights without the MTP layer.
    With each step's outcome known before the next is enqueued (readiness
    given), the steps are what the drafts allow: ``ceil((n - 1) / 2)``
    where every draft is kept."""
    plain, new = model["plain"], 8
    full = np.concatenate([model["prompt"], plain["ids"]])
    if drafts == "oracle":
        _oracle(monkeypatch, full)
    if drafts == "mixed":
        _oracle(monkeypatch, full, wrong_at=lambda p: p % 3 == 0)
    monkeypatch.setattr(type(jnp.zeros(0)), "is_ready", lambda self: self.block_until_ready() is not None)
    out, moved = _generate(model, new)
    assert np.array_equal(out["ids"], plain["ids"]) and np.abs(out["logits"] - plain["logits"]).max() < TOLERANCE
    steps, kept = moved["gen_decode_steps"], moved["mtp_drafts_accepted"]
    assert steps + kept == new - 1 or (steps + kept == new and kept > 0)  # the last step's second token may be past the end
    if drafts == "oracle":
        assert steps == math.ceil((new - 1) / 2) and kept == steps
    if drafts == "mixed":
        assert 0 < kept < steps < new - 1
    if drafts == "seeded":
        assert kept <= 1 and steps >= new - 2


def test_a_prompt_that_ends_near_the_cache_end_writes_nothing_past_it(model, monkeypatch):
    """A prompt of ``positions - 8`` and 8 new tokens, every draft kept, and
    the host enqueuing without seeing a step finish: seven steps, four of
    them past the answer's end, whose length is held under the cache's end;
    the answer is plain greedy decoding's and the other slot is untouched."""
    cfg = model["cfg"]
    prompt = np.random.default_rng(5).integers(1000, GROUP["vocab_size"], size=POSITIONS - 8).astype(np.int32)
    plain = JittedDecoder(model["plain_cfg"], params=model["plain_params"], slots=2, positions=POSITIONS, chunk_buckets=(8, 16)).generate(prompt, 8)
    _oracle(monkeypatch, np.concatenate([prompt, plain["ids"]]))
    lengths = []
    real = decoder.verify_step

    def watched(params, token, draft, cache, slot, length, *, config):
        out = real(params, token, draft, cache, slot, length, config=config)
        jax.debug.callback(lambda n: lengths.append(int(n)), out[4][0])
        return out

    monkeypatch.setattr(decoder, "verify_step", watched)
    monkeypatch.setattr(type(jnp.zeros(0)), "is_ready", lambda self: False)
    executor = JittedDecoder(cfg, params=model["params"], slots=2, positions=POSITIONS, chunk_buckets=(8, 16))
    other = np.asarray(executor.cache["latent"][:, 1])
    before = devctr.snapshot()
    out = executor.generate(prompt, 8)
    moved = _moved(before)
    assert np.array_equal(out["ids"], plain["ids"]) and np.abs(out["logits"] - plain["logits"]).max() < TOLERANCE
    assert moved["gen_decode_steps"] == 7 and moved["mtp_drafts_accepted"] >= 4
    assert lengths[:3] == [POSITIONS - 6, POSITIONS - 4, POSITIONS - 2] and max(lengths) == POSITIONS - 2
    assert np.array_equal(np.asarray(executor.cache["latent"][:, 1]), other)


@pytest.mark.parametrize("program", ["jit__prefill_chunk", "jit__decode_token"])
def test_the_drafting_programs_keep_the_module_names_the_benchmark_reads(model, program):
    executor = _executor(model)
    if program == "jit__prefill_chunk":
        lowered = executor._prefill.lower(
            executor.params, np.zeros(8, np.int32), np.int32(0), executor.cache, np.int32(0), np.int32(0), np.int32(8), np.bool_(True)
        )
    else:
        state = (np.int32(8), np.zeros(1, np.int32), np.zeros(1, np.int32))
        lowered = executor._decode.lower(executor.params, state, executor.cache, np.int32(0))
    assert lowered.as_text().split("module @", 1)[1].split(" ", 1)[0] == program


def test_warm_runs_both_programs_and_a_generation_then_compiles_nothing(model):
    executor = _executor(model)
    executor.warm()
    before = devctr.compile_count()
    out, moved = _generate(model, 5, executor)
    assert devctr.compile_count() == before and moved["span_count.generate_keep"] == 1 and moved["gen_logit_rows"] == 5


def test_the_chat_keeps_each_generations_drafts(model):
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    chat = TPUDecoderChat(config=model["cfg"], params=model["params"], max_new_tokens=4, slots=2, positions=POSITIONS, chunk_buckets=(8, 16))
    text = chat.__wrapped__("What colour are bananas, then?")
    (kept,) = chat.recent_generations()
    assert [f"t{i}" for i in kept["ids"]] == text.split() and kept["prompt_ids"] == family.token_ids("What colour are bananas, then?", 1280)
    n = len(kept["prompt_ids"])
    assert list(kept["drafts"]["positions"]) == list(range(n - 1, n + 3)) and kept["drafts"]["logits"].shape == (4, decoder.DRAFT_KEPT)


def test_a_share_of_the_experts_is_the_references_share(model):
    """The experts 4-11 of the router's 16 held here, in the main layers and
    the MTP layer alike: the program against the reference given the same
    share."""
    group = dict(GROUP, experts_held=8, expert_offset=4)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), family.make_params(group, 7))
    assert np.array_equal(np.asarray(params["layers"][1]["experts"]["up"]), np.asarray(model["params"]["layers"][1]["experts"]["up"][4:12]))
    before = devctr.snapshot()
    out = JittedDecoder(config_of(group), params=params, slots=1, positions=POSITIONS, chunk_buckets=(8, 16)).generate(model["prompt"], 3)
    moved = _moved(before)
    assert 0 < moved["moe_rows_here"] < moved["moe_rows_routed"]
    seq = np.concatenate([model["prompt"], out["ids"]])
    main, draft = family.reference_draft_logits(params, group, [seq], [[20, 21, 22]], [list(out["drafts"]["positions"])], q_block=16)
    assert np.abs(out["logits"] - main[0]).max() < TOLERANCE
    assert max(np.abs(out["drafts"]["logits"][i] - draft[0][i][out["drafts"]["ids"][i]]).max() for i in range(3)) < TOLERANCE


def skip_mtp_layer(monkeypatch):
    """The MTP layer left out: the draft taken from the main model's own
    final-normed row (its input projection and its block do nothing)."""

    def passed(real):
        def layer(h, lp, rows_all, *args):
            if rows_all.shape[0] == 1:  # the MTP layer's cache: one layer
                return h, rows_all, jnp.zeros((len(decoder.STATS),), jnp.int32)
            return real(h, lp, rows_all, *args)

        return layer

    monkeypatch.setattr(decoder, "_mtp_input", lambda params, next_ids, normed, pos, cfg: normed)
    monkeypatch.setattr(decoder, "_chunk_layer", passed(decoder._chunk_layer))
    monkeypatch.setattr(decoder, "_step_layer", passed(decoder._step_layer))


def _hnorm_dropped(params, next_ids, normed, pos, cfg):
    """``RMS(ĥ; w_h)`` left out: the main model's row goes in as it is."""
    mp = params["mtp"]
    embedded = jnp.where((pos == 0)[:, None], 0.0, params["embed"][next_ids].astype(jnp.float32))
    x = jnp.concatenate([decoder._rms(embedded, mp["enorm"], cfg.rms_norm_eps), normed], axis=-1).astype(cfg.dtype)
    return decoder._mm("tc,cd->td", x, mp["eh_proj"])


def _halves_swapped(params, next_ids, normed, pos, cfg, _real=decoder._mtp_input):
    h = cfg.hidden_size
    w = params["mtp"]["eh_proj"]
    return _real(dict(params, mtp=dict(params["mtp"], eh_proj=jnp.concatenate([w[h:], w[:h]]))), next_ids, normed, pos, cfg)


FAULTS = {
    "mtp_skipped": skip_mtp_layer,
    "hnorm_dropped": lambda monkeypatch: monkeypatch.setattr(decoder, "_mtp_input", _hnorm_dropped),
    "eh_halves_swapped": lambda monkeypatch: monkeypatch.setattr(decoder, "_mtp_input", _halves_swapped),
    "next_token_unseen": lambda monkeypatch: monkeypatch.setattr(decoder, "_mtp_input", lambda params, next_ids, normed, pos, cfg: normed),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_in_the_mtp_layer_is_another_model(model, monkeypatch, fault):
    """Each of the MTP layer's pieces shows in its drafts: the layer left
    out, its ``hnorm`` dropped, ``W_eh``'s halves swapped, its input the main
    row alone: the draft logits move by a tenth of the reference's spread or
    more at the median draft, while the main rows stay."""
    FAULTS[fault](monkeypatch)
    out, _ = _generate(model, 4)
    seq = np.concatenate([model["prompt"], out["ids"]])
    main, draft = family.reference_draft_logits(model["params"], GROUP, [seq], [[20, 21, 22, 23]], [list(out["drafts"]["positions"])], q_block=16)
    assert np.abs(out["logits"] - main[0]).max() < TOLERANCE
    gaps = [np.abs(out["drafts"]["logits"][i] - draft[0][i][out["drafts"]["ids"][i]]).max() / draft[0][i].std() for i in range(4)]
    assert np.median(gaps) > SEEN, gaps


def test_bfloat16_stays_near_the_reference(model):
    """The serving type: bfloat16 weights and caches, float32 accumulation."""
    cfg = dataclasses.replace(model["cfg"], dtype=jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, model["params"])
    out = JittedDecoder(cfg, params=params, slots=1, positions=POSITIONS, chunk_buckets=(8, 16)).generate(model["prompt"], 4)
    seq = np.concatenate([model["prompt"], out["ids"]])
    main, draft = family.reference_draft_logits(params, GROUP, [seq], [[20, 21, 22, 23]], [list(out["drafts"]["positions"])], q_block=16)
    assert np.abs(out["logits"] - main[0]).max() < 0.25 * main[0].std()
    assert max(np.abs(out["drafts"]["logits"][i] - draft[0][i][out["drafts"]["ids"][i]]).max() / draft[0][i].std() for i in range(4)) < 0.25


#: sha256 of ``as_text()`` of the DeepSeek toy's two programs (``test_decoder.py``'s group, float32), lowered on the
#: CPU from the module as it stood before the indexer became optional: the programs are the same text
DEEPSEEK_PROGRAMS = {"prefill": "4e71566ee89aa721", "decode": "35ec376bc99aada3"}


def test_a_deepseek_toy_keeps_its_indexer_and_traces_as_before():
    from tests.test_decoder import GROUP as DEEPSEEK, config_of as deepseek_config, float32_params as deepseek_params

    cfg = deepseek_config(DEEPSEEK)
    executor = JittedDecoder(cfg, params=deepseek_params(DEEPSEEK), slots=2, positions=POSITIONS, chunk_buckets=(8, 16))
    assert not executor.drafting and set(executor.cache) == {"latent", "index_k"}
    one = np.zeros(1, np.int32)
    texts = {
        "prefill": executor._prefill.lower(executor.params, np.zeros(16, np.int32), executor.cache, np.int32(0), np.int32(0), np.int32(8), np.bool_(True)),
        "decode": executor._decode.lower(executor.params, one, executor.cache, one, one),
    }
    assert {k: hashlib.sha256(t.as_text().encode()).hexdigest()[:16] for k, t in texts.items()} == DEEPSEEK_PROGRAMS

"""The generation stage's executor: per-request state on the device and
the programs that fill and read it.

:class:`JittedDecoder` stands beside :class:`JittedEncoder` and keeps the
same discipline.  What a request's state is, and what the two programs do
with it, is the architecture's: the module that defines the configuration's
class (:mod:`pathway_tpu.models.decoder`: for every layer latent rows and,
where there is an indexer, indexer keys by position, and an MTP layer's
latent rows where the configuration drafts (DeepSeek-V3.2-Exp and
GLM-4.7-Flash); :mod:`pathway_tpu.models.hybrid_decoder`: recurrent states,
rings, one layer's keys and values, each of its own shape and lifetime;
:mod:`pathway_tpu.models.shortcut_moe_decoder`: two caches of latent rows a
layer, one an attention sublayer; :mod:`pathway_tpu.models.window_moe_decoder`:
keys and values by position for its global layers and a ring for each window
layer) gives ``init_cache``, ``prefill``, ``decode_step``, the ``STATS`` both
count and ``DISPATCH_TOKENS``: four architectures give the five names, and
the third and fourth needed no edit here.  The executor owns the rest:
the state is pre-sized and never grows, ``slots`` sequences of
``positions`` tokens, updated in place through donation as the index slab
is.  Shapes come from a small fixed set: a prompt is cut into chunks of the
``chunk_buckets`` (:meth:`plan`: the cheapest cover), each one execution of
the prefill program of its bucket, and every decode step is one execution of
the one decode program.  :meth:`warm` runs all of them once, so that a
serving window compiles nothing.

:meth:`generate` is the loop that yields tokens: the prompt's chunks are
enqueued back to back, then the decode steps, each taking the token the
step before it chose on the device, so the host runs ahead of the chip.
Outside the lock that orders the enqueues it starts every output's copy to
the host and waits for the prompt's logits (span ``generate_prefill``), then
walks the steps in order (``generate_decode``): each step's row of logits
lands in its place of one host array while the chip runs the steps after
it, so that once the last step has run only its row, the ids and the
counters are left (``generate_keep``).  Requests generate one after
another on the device (the engine gives this stage one request an epoch);
the slots are taken in turn.

**The drafting loop.**  Where the architecture's module also offers
``prefill_drafted`` and ``verify_step`` and the configuration has an MTP
layer (``num_nextn_predict_layers``), a step no longer yields one token: it
verifies the MTP layer's draft beside the pending token and emits one token
or two (``models/decoder.py`` has the equations).  The sequence's length,
its pending token and its draft are carried on the device from program to
program; the host never waits on a step before it enqueues the next, and
stops once the tokens of the steps it has seen finish (``is_ready``) and one
for each step still running reach the answer's length; what a step emits
past it is dropped, and a step's length is held under the cache's end.  The
other architectures keep the loop above as it was.  Counters
``mtp_drafts`` / ``mtp_drafts_accepted``; ``gen_decode_steps`` counts verify
steps.

The two programs lower as ``jit__prefill_chunk`` and ``jit__decode_token``
(the drafting loop's too): the benchmark finds their device time by these
names and a tier-1 test holds them.
"""

from __future__ import annotations

import collections
import sys
import threading
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.internals import device_counters as _devctr
from pathway_tpu.internals import tracing as _tracing
from pathway_tpu.parallel.mesh import require_single_process

__all__ = ["JittedDecoder"]


class JittedDecoder:
    """Holds the decoder's params, its caches and its compiled programs.

    ``generate(prompt_ids, max_new_tokens)`` -> the ids chosen (greedy, over
    the held slice of the vocabulary) and the float32 logits each was
    chosen from."""

    def __init__(
        self,
        config: Any,
        *,
        params: Any,
        slots: int = 8,
        positions: int = 8704,
        chunk_buckets: Sequence[int] = (512, 2048, 2560),
    ):
        require_single_process("JittedDecoder")
        buckets = tuple(sorted(chunk_buckets))
        unit = buckets[0]
        if positions % unit or unit % config.key_block or any(b % unit for b in buckets):
            raise ValueError(
                f"chunk buckets {buckets} must be multiples of the smallest, which must divide "
                f"positions {positions} and be a multiple of the key block {config.key_block}"
            )
        self.config = config
        self.architecture = arch = sys.modules[type(config).__module__]  # the module whose configuration this is
        self.params = params
        self.slots = slots
        self.positions = positions
        self.chunk_buckets = buckets
        self.cache = arch.init_cache(config, slots, positions)
        self._lock = threading.Lock()  # the caches are one donated state
        self._turn = 0

        def _prefill_chunk(params, ids, cache, slot, start, length, last):
            logits, cache, stats = arch.prefill(params, ids, cache, slot, start, length, last, config=config)
            return jnp.argmax(logits).astype(jnp.int32)[None], logits, cache, stats

        def _decode_token(params, token, cache, slot, length):
            logits, cache, stats = arch.decode_step(params, token, cache, slot, length, config=config)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits[0], cache, stats

        # the drafting loop: where the architecture offers the speculative step and the configuration has an MTP layer
        self.drafting = hasattr(arch, "verify_step") and getattr(config, "num_nextn_predict_layers", 0) > 0
        if self.drafting:
            self._prefill, self._decode = self._drafting_programs()
            return
        self._prefill = jax.jit(_prefill_chunk, donate_argnums=(2,))
        self._decode = jax.jit(_decode_token, donate_argnums=(2,))

    def _drafting_programs(self):
        """The two programs of the drafting loop, under the same names.  Each
        returns the sequence's state ``(length, pending token, draft)``,
        which stays on the device from one to the next, the float32 row(s)
        kept, and one small int32 array the host reads: the prefill's
        ``[token, draft ids (8), draft logits (8, bit for bit), counts]``, a
        step's ``[tokens emitted, the two ids, draft ids, draft logits,
        counts]``."""
        arch, config = self.architecture, self.config
        bits = lambda values: jax.lax.bitcast_convert_type(values, jnp.int32)

        def _prefill_chunk(params, ids, following, cache, slot, start, length, last):
            token, logits, (values, top), cache, stats = arch.prefill_drafted(
                params, ids, following, cache, slot, start, length, last, config=config
            )
            return (start + length, token, top[:1]), logits, jnp.concatenate([token, top, bits(values), stats]), cache

        def _decode_token(params, state, cache, slot):
            length, token, draft = state
            emitted, chosen, rows, (values, top), state, cache, stats = arch.verify_step(
                params, token, draft, cache, slot, length, config=config
            )
            return state, rows[0], rows[1], jnp.concatenate([emitted[None], chosen, top, bits(values), stats]), cache

        return jax.jit(_prefill_chunk, donate_argnums=(3,)), jax.jit(_decode_token, donate_argnums=(2,))

    # ------------------------------------------------------------------
    def plan(self, tokens: int) -> list[tuple[int, int, int]]:
        """A prompt of ``tokens`` tokens as ``(start, real tokens, bucket)``
        chunks: the cover by buckets that costs least, where a dispatch costs
        its bucket's tokens and the architecture's ``DISPATCH_TOKENS`` more
        (only the last chunk may hold padding, and none may pass the cache's
        end)."""
        unit = self.chunk_buckets[0]
        dispatch = self.architecture.DISPATCH_TOKENS / unit
        need, room = -(-tokens // unit), self.positions // unit
        sizes = [b // unit for b in self.chunk_buckets]
        best: list = [(0, ())]  # best[n]: (cost, buckets) for the prompt's last n units
        for n in range(1, need + 1):
            best.append(min(
                (dispatch + b + best[max(n - b, 0)][0], (-b, *best[max(n - b, 0)][1]))
                for b in sizes if need - n + b <= room
            ))
        chunks, start = [], 0
        for b in best[need][1]:
            chunks.append((start, min(tokens - start, -b * unit), -b * unit))
            start += -b * unit
        return chunks

    def generate(self, prompt_ids: Sequence[int], max_new_tokens: int) -> dict:
        """Prefill ``prompt_ids`` and choose ``max_new_tokens`` tokens, each
        the largest logit over the held vocabulary.  Returns ``{"ids": int32
        [n], "logits": float32 [n, vocab_held]}``: row i is what ids[i] was
        chosen from."""
        prompt = np.asarray(prompt_ids, np.int32)
        if not 0 < prompt.size <= self.positions - max_new_tokens or max_new_tokens < 1:
            raise ValueError(
                f"a prompt of {prompt.size} tokens and {max_new_tokens} new ones do not fit "
                f"the cache's {self.positions} positions"
            )
        chunks = self.plan(prompt.size)
        if self.drafting:
            return self._generate_drafted(prompt, max_new_tokens, chunks)
        steps = max_new_tokens - 1
        with _tracing.span("generate_prefill") as sp:
            sp.args = {"tokens": int(prompt.size), "chunks": len(chunks)}
            # the lock covers the enqueues alone: the caches are one donated state, and the device runs programs
            # in the order they were enqueued, so whoever enqueues next finds the caches as this request leaves them
            with self._lock:
                slot = self._turn % self.slots
                self._turn += 1
                slot_arr = np.asarray([slot], np.int32)
                stats = []
                for start, real, bucket in chunks:
                    ids = np.zeros(bucket, np.int32)
                    ids[:real] = prompt[start : start + real]
                    _devctr.record_h2d(ids.nbytes)
                    token, logits, self.cache, st = self._prefill(
                        self.params, ids, self.cache, np.int32(slot), np.int32(start), np.int32(real), np.bool_(start + real == prompt.size)
                    )
                    prefilled = _tracing.chip.ticket()
                    stats.append(st)
                rows, tokens = [logits], [token]
                decoded = prefilled
                for i in range(steps):
                    token, logits, self.cache, st = self._decode(
                        self.params, token, self.cache, slot_arr, np.asarray([prompt.size + i], np.int32)
                    )
                    decoded = _tracing.chip.ticket()
                    rows.append(logits)
                    tokens.append(token)
                    stats.append(st)
            # the copies start once the lock is released, so that no enqueue waits behind them: each array then
            # leaves the device as soon as the step that makes it has run
            for array in (*rows, *tokens, *stats):
                array.copy_to_host_async()
            rows[0].block_until_ready()
            _tracing.chip.collected(prefilled)
        # each row lands in its place of the one host array while the chip runs the steps after it
        logits = np.empty((max_new_tokens, rows[0].shape[-1]), np.float32)
        ids = np.empty(max_new_tokens, np.int32)
        counted = np.zeros(len(self.architecture.STATS), np.int64)
        made = [stats[: len(chunks)], *([st] for st in stats[len(chunks) :])]  # row i's dispatches' counts

        def land(i: int) -> None:
            logits[i] = rows[i]
            ids[i : i + 1] = tokens[i]
            for st in made[i]:
                np.add(counted, st, out=counted)

        early = 0
        with _tracing.span("generate_decode") as sp:
            sp.args = {"steps": steps}
            for i in range(steps):
                land(i)
                early += not rows[-1].is_ready()
            rows[-1].block_until_ready()
        with _tracing.span("generate_keep"):
            land(steps)
            _tracing.chip.collected(decoded)
            _devctr.record_d2h(logits.nbytes)
            _devctr.bump(
                gen_requests=1,
                gen_prompt_tokens=prompt.size,
                gen_prompt_tokens_padded=sum(bucket for _s, _r, bucket in chunks),
                gen_prefill_dispatches=len(chunks),
                gen_new_tokens=max_new_tokens,
                gen_decode_steps=steps,
                gen_logit_rows=max_new_tokens,
                gen_logit_rows_early=early,
                **dict(zip(self.architecture.STATS, (int(c) for c in counted))),
            )
        return {"ids": ids, "logits": logits}

    def _generate_drafted(self, prompt: np.ndarray, max_new_tokens: int, chunks: list) -> dict:
        """:meth:`generate` through the drafting loop.  Each step emits one
        or two tokens, so the host enqueues steps, never waiting on one,
        until the tokens of the steps it has seen finish (``is_ready``) and
        one for each still running reach ``max_new_tokens`` (at most
        ``max_new_tokens - 1`` steps); what a step emits past that is
        dropped.  Besides ``ids`` and ``logits`` it returns ``drafts``: each
        draft made at a position of the answer whose next token was emitted,
        ``positions`` [n] and its ``DRAFT_KEPT`` largest logits (``logits``
        [n, k] float32) and their ``ids`` [n, k], the draft first."""
        M, P, S = max_new_tokens, prompt.size, len(self.architecture.STATS)
        K = self.architecture.DRAFT_KEPT
        with _tracing.span("generate_prefill") as sp:
            sp.args = {"tokens": int(P), "chunks": len(chunks)}
            with self._lock:  # as in generate: the enqueues alone
                slot = np.int32(self._turn % self.slots)
                self._turn += 1
                smalls = []
                for start, real, bucket in chunks:
                    ids = np.zeros(bucket, np.int32)
                    ids[:real] = prompt[start : start + real]
                    following = prompt[start + real] if start + real < P else 0  # the next chunk's first id
                    _devctr.record_h2d(ids.nbytes)
                    state, first, small, self.cache = self._prefill(
                        self.params, ids, np.int32(following), self.cache, slot, np.int32(start), np.int32(real), np.bool_(start + real == P)
                    )
                    prefilled = decoded = _tracing.chip.ticket()
                    smalls.append(small)
                steps, running, finished = [], collections.deque(), 1  # finished: tokens of the steps seen to have finished
                while len(steps) < M - 1:
                    while running and running[0].is_ready():
                        finished += int(np.asarray(running.popleft())[0])
                    if finished + len(running) >= M:
                        break
                    state, row, second, small, self.cache = self._decode(self.params, state, self.cache, slot)
                    decoded = _tracing.chip.ticket()
                    steps.append((row, second, small))
                    running.append(small)
            for array in (first, *smalls, *(a for row, _second, small in steps for a in (row, small))):
                array.copy_to_host_async()
            first.block_until_ready()
            _tracing.chip.collected(prefilled)
        logits = np.empty((M, first.shape[-1]), np.float32)
        ids = np.empty(M, np.int32)
        counted = np.zeros(S, np.int64)
        draft_at, draft_ids, draft_logits = [], [], []
        for small in smalls:
            np.add(counted, np.asarray(small)[-S:], out=counted)
        head = np.asarray(smalls[-1])
        logits[0], ids[0] = first, head[0]
        kept = [1, P]  # rows kept so far, and the sequence's length once they are in the cache

        def draft(p: int, top: np.ndarray) -> None:
            if p <= P + M - 2:  # a position whose next token is the answer's
                draft_at.append(p)
                draft_ids.append(top[:K])
                draft_logits.append(top[K : 2 * K].view(np.float32))

        def land(row, second, small) -> int:
            """One step's rows into their places; returns the draft it kept (0 or 1)."""
            small = np.asarray(small)
            emitted, useful = int(small[0]), kept[0] < M
            for j in range(emitted if useful else 0):
                if kept[0] < M:
                    logits[kept[0]], ids[kept[0]] = row if j == 0 else second, small[1 + j]
                    kept[0] += 1
            np.add(counted, small[-S:], out=counted)
            if useful:
                kept[1] += emitted
                draft(kept[1] - 1, small[3:])
            return emitted - 1

        draft(P - 1, head[1:])
        accepted, early = 0, 0
        with _tracing.span("generate_decode") as sp:
            for row, second, small in steps[:-1]:
                before = kept[0]
                accepted += land(row, second, small)
                early += (kept[0] - before) * (not steps[-1][2].is_ready())
            if steps:
                steps[-1][2].block_until_ready()
            last_kept = int(np.asarray(steps[-1][2])[0]) - 1 if steps else 0
            sp.args = {"steps": len(steps), "mtp_drafts": len(steps), "mtp_drafts_accepted": accepted + last_kept}
        with _tracing.span("generate_keep"):
            if steps:
                accepted += land(*steps[-1])
            _tracing.chip.collected(decoded)
            _devctr.record_d2h(logits.nbytes)
            _devctr.bump(
                gen_requests=1,
                gen_prompt_tokens=P,
                gen_prompt_tokens_padded=sum(bucket for _s, _r, bucket in chunks),
                gen_prefill_dispatches=len(chunks),
                gen_new_tokens=M,
                gen_decode_steps=len(steps),
                gen_logit_rows=M,
                gen_logit_rows_early=early,
                mtp_drafts=len(steps),
                mtp_drafts_accepted=accepted,
                **dict(zip(self.architecture.STATS, (int(c) for c in counted))),
            )
        drafts = {
            "positions": np.asarray(draft_at, np.int32),
            "ids": np.asarray(draft_ids, np.int32).reshape(-1, K),
            "logits": np.asarray(draft_logits, np.float32).reshape(-1, K),
        }
        return {"ids": ids, "logits": logits, "drafts": drafts}

    def warm(self, max_new_tokens: int = 2) -> None:
        """Run every program once: a prompt of each chunk bucket's size (one
        chunk of that bucket), each followed by a decode step."""
        for bucket in self.chunk_buckets:
            self.generate(np.ones(min(bucket, self.positions - max_new_tokens), np.int32), max_new_tokens)

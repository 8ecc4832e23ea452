"""Batched jitted model executor: the TPU replacement for per-row torch.

The reference embeds/reranks one row at a time inside a torch UDF
(``xpacks/llm/embedders.py:270-327``, ``rerankers.py:186-235``).  Here a
whole epoch's rows are tokenized into one bucketed batch and pushed
through a single jit-compiled flax program; with a mesh, the batch is
data-parallel over ``"data"`` and the params tensor-parallel over
``"model"`` (see :func:`pathway_tpu.models.encoder_param_specs`).

A tokenized batch shares one padded length, the bucket of its longest
text.  Where most texts are much shorter than that, several are laid end
to end in one row of the same length (:meth:`JittedEncoder._pack`) and
the program attends within each text, so the device multiplies tokens of
texts and not padding.  Whether a batch is packed is read from its
lengths: exactly when that dispatches fewer padded tokens.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from pathway_tpu.models.encoder import (
    CrossEncoderModel,
    EncoderConfig,
    TextEncoderModel,
    encoder_param_specs,
)
from pathway_tpu.internals import device_counters as _devctr
from pathway_tpu.internals import tracing as _tracing
from pathway_tpu.models.tokenizer import Tokenizer, get_tokenizer
from pathway_tpu.ops.bucketing import bucket_size
from pathway_tpu.parallel.mesh import require_single_process

__all__ = ["JittedEncoder"]

#: device memory assumed where the backend reports none (the CPU): one
#: v5e chip's 16 GB, so tests and the chip split batches alike
_ASSUMED_DEVICE_BYTES = 16 * 2**30

#: most texts one packed row may hold: their numbers upload as uint8
_MAX_SEGMENTS = 255


class JittedEncoder:
    """Holds (possibly sharded) params + compiled apply fns per shape bucket.

    cross=False: ``encode(texts) -> [n, hidden] float32`` embeddings.
    cross=True:  ``score_pairs(queries, docs) -> [n] float32`` logits.
    """

    def __init__(
        self,
        config: EncoderConfig | None,
        *,
        cross: bool = False,
        tokenizer: Tokenizer | None = None,
        model_name: str | None = None,
        mesh: Mesh | None = None,
        data_axis: str = "data",
        model_axis: str = "model",
        max_batch: int = 1024,
        max_len: int | None = None,
        seed: int = 0,
        params: Any = None,
        checkpoint_dir: str | None = None,
        pipeline_depth: int = 2,
        sequence_axis: str | None = None,
    ):
        require_single_process("JittedEncoder")
        #: sequence_axis: shard the SEQUENCE dimension over this mesh
        #: axis and run ring attention inside every layer — the
        #: long-document path: max_len may exceed one device's attention
        #: memory (it must divide by the axis size).  Mutually exclusive
        #: with sharding the batch over the same axis.
        #: chunks kept in flight before collecting a readback.  2 is one
        #: computing + one draining; each extra slot overlaps more host
        #: tokenization with device work at the cost of one more
        #: resident batch.
        self.pipeline_depth = max(1, pipeline_depth)
        if checkpoint_dir is not None:
            # real pretrained weights: config/params/vocab all from the
            # local HF checkpoint directory (models/convert.py).  Pass
            # config=None to let config.json decide pooling (BGE -> cls);
            # an explicit config only overrides pool/dtype here.
            import dataclasses as _dc

            from pathway_tpu.models import convert as _convert
            from pathway_tpu.models.wordpiece import WordPieceTokenizer
            import os as _os

            if params is not None:
                raise ValueError(
                    "pass either params= or checkpoint_dir=, not both — "
                    "explicit params would be silently replaced"
                )
            user_cfg = config
            config = _convert.config_from_hf(
                checkpoint_dir,
                pool=user_cfg.pool if user_cfg is not None else None,
                num_labels=1 if cross else 0,
            )
            config = _dc.replace(config, normalize=not cross)
            if user_cfg is not None:
                config = _dc.replace(config, dtype=user_cfg.dtype)
            params = _convert.convert_bert_checkpoint(
                _convert.load_state_dict(checkpoint_dir), config
            )
            vocab = _os.path.join(checkpoint_dir, "vocab.txt")
            if tokenizer is None and _os.path.exists(vocab):
                tokenizer = WordPieceTokenizer(vocab)
        elif config is None:
            raise ValueError("config is required without checkpoint_dir")
        self.sequence_axis = sequence_axis
        if sequence_axis is not None:
            import dataclasses as _dc

            if mesh is None or sequence_axis not in mesh.shape:
                raise ValueError(
                    "sequence_axis requires a mesh containing that axis"
                )
            config = _dc.replace(
                config, seq_mesh=mesh, seq_axis=sequence_axis
            )
        self.config = config
        self.cross = cross
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.max_batch = max_batch
        self.max_len = max_len or config.max_len
        if sequence_axis is not None:
            n_seq = mesh.shape[sequence_axis]
            if self.max_len % n_seq != 0:
                raise ValueError(
                    f"max_len {self.max_len} must divide the "
                    f"{sequence_axis!r} axis size {n_seq}"
                )
        self.tokenizer = tokenizer or get_tokenizer(model_name, config.vocab_size)
        self.model = (CrossEncoderModel if cross else TextEncoderModel)(config)

        if params is None:
            rng = jax.random.PRNGKey(seed)
            dummy = jnp.zeros((1, 8), jnp.int32)
            init_model = self.model
            if sequence_axis is not None:
                # init with the local-attention twin: identical params
                # (ring attention adds no parameters), no shard_map at
                # init time
                import dataclasses as _dc

                init_model = (CrossEncoderModel if cross else TextEncoderModel)(
                    _dc.replace(config, seq_mesh=None)
                )
            params = init_model.init(rng, dummy, jnp.ones((1, 8), jnp.int32))
        # batch layout: DP shards rows over data_axis; the SP long-doc
        # path instead shards the SEQUENCE dimension over sequence_axis
        in_spec = (
            P(None, sequence_axis)
            if sequence_axis is not None
            else P(data_axis, None)
        )
        if mesh is not None and model_axis in mesh.shape:
            specs = encoder_param_specs(params, model_axis)
            shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
            params = jax.device_put(params, shardings)
            self._in_batch_sharding = NamedSharding(mesh, in_spec)
            self._out_sharding = NamedSharding(mesh, P())
        elif mesh is not None:
            params = jax.device_put(
                params, jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
            )
            self._in_batch_sharding = NamedSharding(mesh, in_spec)
            self._out_sharding = NamedSharding(mesh, P())
        else:
            self._in_batch_sharding = None
            self._out_sharding = None
        self.params = params
        # token ids upload as int16 when the vocab permits (mask/type as
        # uint8): 3x fewer host->device bytes per chunk
        # (pathway_tpu_h2d_bytes_total); the cast back to int32 is fused
        # into the compiled apply
        self._narrow_ids = config.vocab_size < 2**15

        def _apply_cast(params, ids, mask, tps, first=None):
            # packed rows: ``mask`` numbers each row's texts and ``first``
            # says where each text starts (both programs are this function,
            # so both lower as ``jit__apply_cast``)
            packed = () if first is None else (first,)
            return self.model.apply(
                params,
                ids.astype(jnp.int32),
                mask.astype(jnp.int32),
                tps.astype(jnp.int32),
                *packed,
            )

        self._apply = jax.jit(_apply_cast, out_shardings=self._out_sharding)
        self._dp = 1 if mesh is None else mesh.shape.get(data_axis, 1)
        # activations one dispatch may hold live on a device: a quarter of
        # its memory — params, the index slab and the pipeline's resident
        # batches share the rest
        device = jax.local_devices()[0] if mesh is None else mesh.devices.flat[0]
        stats = device.memory_stats()
        self._dispatch_bytes = (
            (stats or {}).get("bytes_limit") or _ASSUMED_DEVICE_BYTES
        ) // 4

    # ------------------------------------------------------------------
    def _rows_per_dispatch(self, length: int) -> int:
        """Most rows one dispatch may carry at padded length ``length``.

        ``max_batch`` alone does not bound memory: attention holds
        ``[rows, heads, length, length]`` scores, and a whole batch shares
        the length bucket of its longest text (packed or not, a row is that
        long), so one long chunk takes a 1024-row batch to 512 tokens —
        for BGE-large a program with
        10.8 GB of temporaries beside 1.3 GB of params on a 16.9 GB v5e
        (XLA's memory analysis; it compiles, and leaves the index slab
        and every other model 4.8 GB).  Rows are bounded instead by the
        layer's live set per row: one f32 score tensor over this
        device's heads beside eight ``[length, hidden]`` activations.
        That model over-states what XLA assigns on a v5e by 1.25x at 512
        tokens (20.2 MB a row measured) and 1.6x at 128 (1.9 MB), so a
        dispatch stays under its quarter of the device.  A power of two
        per device, so the split adds no shapes beyond the batch
        buckets."""
        if self.sequence_axis is not None:
            return self.max_batch  # ring attention never holds full scores
        cfg = self.config
        tp = 1 if self.mesh is None else self.mesh.shape.get(self.model_axis, 1)
        per_row = length * (
            max(1, cfg.heads // tp) * length * 4
            + 8 * cfg.hidden * np.dtype(cfg.dtype).itemsize
        )
        fit = max(1, self._dispatch_bytes // per_row)
        rows = (1 << (fit.bit_length() - 1)) * self._dp
        return min(self.max_batch, max(rows, max(8, self._dp)))

    def _row_bucket(self, n: int) -> int:
        """Rows a dispatch of ``n`` is padded to: a power of two (8 at the
        least) that divides the data-parallel degree."""
        b = bucket_size(n, min_bucket=max(8, self._dp))
        return ((b + self._dp - 1) // self._dp) * self._dp

    def _padded_rows(self, n: int, per_dispatch: int) -> int:
        """Rows that ``n`` rows cost, split ``per_dispatch`` at a time."""
        full, rest = divmod(n, per_dispatch)
        return full * self._row_bucket(per_dispatch) + (
            self._row_bucket(rest) if rest else 0
        )

    def _pad_batch(self, ids: np.ndarray, mask: np.ndarray, tps: np.ndarray):
        """Round the batch up so it divides the data-parallel degree."""
        n = ids.shape[0]
        b = self._row_bucket(n)
        if b > n:
            pad = ((0, b - n), (0, 0))
            ids = np.pad(ids, pad)
            mask = np.pad(mask, pad)
            tps = np.pad(tps, pad)
        # padded rows must still be valid encoder input: one non-masked token
        # (in packed rows, a one-token text that nothing is pooled from)
        mask[n:, 0] = 1
        return ids, mask, tps, n

    def _dispatch(
        self,
        ids: np.ndarray,
        mask: np.ndarray,
        tps: np.ndarray,
        first: np.ndarray | None = None,
        start_host_copy: bool = True,
    ):
        """Enqueue one padded chunk; returns (device_out, n_real_outputs,
        chip ticket).  With ``first`` the rows are packed (:meth:`_pack`):
        ``mask`` numbers each row's texts and the output has a row per
        text, not per row.  The device->host copy is started immediately
        (non-blocking), so the readback of chunk i overlaps the
        tokenize+compute of chunk i+1, and the dispatch takes a ticket of
        the chip account that :meth:`_readback` collects.
        ``start_host_copy=False`` for consumers that keep the output on
        device (``encode_into``): nothing waits on it, so no ticket (0)."""
        with _tracing.span("encoder_dispatch") as sp:
            tokens = np.count_nonzero(mask)
            ids, mask, tps, rows = self._pad_batch(ids, mask, tps)
            if self.sequence_axis is not None and ids.shape[1] < self.max_len:
                # SP shards the sequence dimension: pad to the full max_len so
                # every device holds an equal block
                pad = ((0, 0), (0, self.max_len - ids.shape[1]))
                ids = np.pad(ids, pad)
                mask = np.pad(mask, pad)
                tps = np.pad(tps, pad)
            rows_padded, length = ids.shape
            n = rows if first is None else first.shape[0]
            sp.args = {"texts": n, "rows": rows, "rows_padded": rows_padded, "length": length}
            _devctr.bump(
                encoder_dispatches=1,
                encoder_segments=n,
                encoder_rows=rows,
                encoder_rows_padded=rows_padded,
                encoder_tokens=tokens,
                encoder_tokens_padded=rows_padded * length,
            )
            if self._narrow_ids:
                ids = ids.astype(np.int16, copy=False)
                mask = mask.astype(np.uint8, copy=False)
                tps = tps.astype(np.uint8, copy=False)
            h2d = ids.nbytes + mask.nbytes + tps.nbytes
            args = [jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(tps)]
            if self._in_batch_sharding is not None:
                args = [jax.device_put(a, self._in_batch_sharding) for a in args]
            if first is not None:
                first = np.pad(first, (0, bucket_size(n) - n)).astype(np.int32)
                h2d += first.nbytes
                args.append(jax.device_put(first, self._out_sharding))
            _devctr.record_h2d(h2d)
            out = self._apply(self.params, *args)
            ticket = 0
            if start_host_copy:
                ticket = _tracing.chip.ticket()
                out.copy_to_host_async()
        return out, n, ticket

    def _run(self, ids: np.ndarray, mask: np.ndarray, tps: np.ndarray) -> np.ndarray:
        out, n, ticket = self._dispatch(ids, mask, tps)
        return self._readback(out, ticket)[:n]

    @staticmethod
    def _readback(out: Any, ticket: int) -> np.ndarray:
        with _tracing.span("encoder_readback"):
            host = np.asarray(out)
            _tracing.chip.collected(ticket)
        _devctr.record_d2h(host.nbytes)
        return host

    def _pack(
        self, ids: np.ndarray, mask: np.ndarray, tps: np.ndarray, rows: int
    ) -> list[tuple[tuple, np.ndarray]] | None:
        """The packed dispatches of one tokenized batch, or None where
        packing dispatches no fewer padded rows than the batch as it is.

        First-fit over the texts sorted longest first, into rows of the
        batch's own length: the plan's shape depends on the multiset of
        lengths alone.  A dispatch is ``(ids, segments, type_ids, first)``,
        at most ``rows`` rows, with the positions in the batch of the texts
        it carries: ``segments`` numbers a row's texts from 1 (0 is
        padding) and ``first[i]`` is the i-th of those texts' first token
        in the flattened rows.  Masks are prefixes (every tokenizer here
        pads on the right)."""
        n, length = ids.shape
        today = self._padded_rows(n, rows)
        if today == self._row_bucket(1):
            return None  # a question, a few texts: already the smallest dispatch
        lens = np.maximum(mask.sum(axis=1), 1)
        if self._padded_rows(-(-int(lens.sum()) // length), rows) >= today:
            return None  # full rows: no packing can save a row
        with _tracing.span("encoder_pack") as sp:
            room = np.full(n, length)
            held = [0] * n
            placed = []  # (row, first token in the flattened rows, number in the row)
            order = np.argsort(-lens, kind="stable")
            for size in lens[order].tolist():
                r = int((room >= size).argmax())
                left = int(room[r])
                held[r] += 1
                placed.append((r, r * length + length - left, held[r]))
                room[r] = left - size if held[r] < _MAX_SEGMENTS else 0
            row_of, start, number = np.empty((3, n), np.int64)
            row_of[order], start[order], number[order] = np.array(placed).T
            n_rows = int(row_of.max()) + 1
            sp.args = {"texts": n, "rows": n_rows, "length": length}
            if self._padded_rows(n_rows, rows) >= today:
                return None
            keep = np.arange(length) < lens[:, None]
            to = (start[:, None] + np.arange(length))[keep]
            flat = np.zeros((3, n_rows * length), ids.dtype)
            flat[0, to] = ids[keep]
            flat[1, to] = np.repeat(number, lens)
            flat[2, to] = tps[keep]
            planes = flat.reshape(3, n_rows, length)
            units = []
            for d in range(0, n_rows, rows):
                at = np.flatnonzero((row_of >= d) & (row_of < d + rows))
                units.append(((*planes[:, d : d + rows], start[at] - d * length), at))
        return units

    def _chunks(
        self, texts: Sequence[str], pair: Sequence[str] | None
    ) -> Iterator[tuple[tuple, np.ndarray]]:
        """Tokenized dispatch units: the arguments of :meth:`_dispatch`
        and the positions in ``texts`` of the outputs it will give.  Up to
        ``max_batch`` texts are tokenized together (they share one padded
        length), packed where that saves rows (:meth:`_pack`; not the
        cross-encoder's pairs, not sequence-parallel rows), and split so
        that no dispatch exceeds :meth:`_rows_per_dispatch` at that
        length."""
        for i in range(0, len(texts), self.max_batch):
            sl = slice(i, i + self.max_batch)
            with _tracing.span("encoder_tokenize"):
                ids, mask, tps = self.tokenizer.encode_batch(
                    texts[sl],
                    pair=None if pair is None else pair[sl],
                    max_len=self.max_len,
                )
            n = ids.shape[0]
            rows = self._rows_per_dispatch(ids.shape[1])
            units = None
            if not self.cross and self.sequence_axis is None:
                units = self._pack(ids, mask, tps, rows)
            if units is None:
                units = [
                    ((ids[j : j + rows], mask[j : j + rows], tps[j : j + rows]),
                     np.arange(j, min(j + rows, n)))
                    for j in range(0, n, rows)
                ]
            for arrays, at in units:
                yield arrays, i + at

    def _run_pipelined(self, texts: list, pair: "list | None") -> np.ndarray:
        """Tokenize/dispatch up to ``self.pipeline_depth`` chunks before
        collecting the oldest readback, so tokenize + device compute +
        host transfer of different chunks all overlap.  One output per
        text, in the order of ``texts``."""
        ordered = None
        inflight: deque = deque()

        def collect():
            nonlocal ordered
            out, n, ticket, at = inflight.popleft()
            host = self._readback(out, ticket)[:n]
            if ordered is None:
                ordered = np.empty((len(texts),) + host.shape[1:], host.dtype)
            ordered[at] = host

        for arrays, at in self._chunks(texts, pair):
            inflight.append((*self._dispatch(*arrays), at))
            if len(inflight) >= self.pipeline_depth:
                collect()
        while inflight:
            collect()
        return ordered

    # ------------------------------------------------------------------
    def encode(self, texts: Sequence[str]) -> np.ndarray:
        """Embed a list of texts -> [n, hidden] float32."""
        if self.cross:
            raise TypeError("cross-encoder executor: use score_pairs()")
        if not texts:
            return np.zeros((0, self.config.hidden), np.float32)
        return self._run_pipelined(list(texts), None)

    def encode_into(self, index: Any, keys: Sequence[Any], texts: Sequence[str]) -> int:
        """Embed ``texts`` and upsert the embeddings into ``index``
        (``ShardedKnnIndex.add_batch_device``) entirely on device — no
        embedding ever crosses the host link.  The reference embedder
        reads every vector back through host memory before indexing
        (python/pathway/xpacks/llm/embedders.py:270-327); on TPU the
        index slab lives in the same HBM, so the chunk pipeline here
        only ships token ids up and nothing down.  Returns the number of
        rows indexed."""
        if self.cross:
            raise TypeError("cross-encoder executor: use score_pairs()")
        texts = list(texts)
        keys = list(keys)
        if len(keys) != len(texts):
            raise ValueError("keys and texts must align")
        if not texts:
            return 0
        inflight: deque = deque()
        for arrays, at in self._chunks(texts, None):
            out, n, _ = self._dispatch(*arrays, start_host_copy=False)
            inflight.append((out, n, [keys[a] for a in at]))
            if len(inflight) >= self.pipeline_depth:
                out, n, kchunk = inflight.popleft()
                index.add_batch_device(kchunk, out, n_valid=n)
        while inflight:
            out, n, kchunk = inflight.popleft()
            index.add_batch_device(kchunk, out, n_valid=n)
        return len(texts)

    def score_pairs(self, queries: Sequence[str], docs: Sequence[str]) -> np.ndarray:
        """Cross-encoder scores for aligned (query, doc) pairs -> [n]."""
        if not self.cross:
            raise TypeError("bi-encoder executor: use encode()")
        if len(queries) != len(docs):
            raise ValueError("queries and docs must align")
        if not queries:
            return np.zeros((0,), np.float32)
        return self._run_pipelined(list(queries), list(docs))

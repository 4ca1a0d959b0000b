"""Draft-model speculative decoding: a small in-family model drafts, the
target verifies (r3 VERDICT next #6).

Prompt-lookup speculation (``EngineCore._draft_for``) only accelerates
repetitive stretches; a real draft model (llama-3.2-1B drafting for 8B)
speculates on NOVEL text too. The engine's verify machinery is unchanged —
``_run_decode_spec`` accepts the agreeing prefix of ANY draft — this module
only produces better drafts:

- The worker keeps its own paged KV pool (own page size/pool — the draft's
  dims differ from the target's) and a per-request count of COMMITTED
  tokens whose K/V it has written.
- Each round, per request: (1) sync — feed committed tokens the draft has
  not seen (everything but the last) through the chunked prefill step;
  (2) draft — run ``k`` greedy decode steps in ONE ``_decode_multi``
  dispatch (on-device sampling loop, single host sync), starting from the
  last committed token.
- Speculative K/V written during drafting is position-addressed, so the
  next round's sync simply overwrites the slots of rejected tokens — the
  same recovery trick the target engine uses for its own rejected drafts.

TPU shape discipline: sync chunks pad to a fixed length and drafting is a
fixed-K scan, so the worker adds exactly two compiled programs per pool
geometry regardless of traffic.

No reference counterpart: RunbookAI calls hosted LLM APIs (SURVEY §2.2).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from runbookai_tpu.engine.kv_cache import KVCacheManager


class DraftWorker:
    """Owns the draft model's params + KV pool; produces per-request drafts."""

    def __init__(
        self,
        cfg,
        params,
        max_batch_slots: int,
        max_seq_len: int,
        page_size: int = 16,
        num_pages: int = 1024,
        prefill_chunk: int = 256,
        block_pages: int = 16,
        attn_impl: str = "xla",
    ):
        self.cfg = cfg
        self.params = params
        self.ecfg_page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.block_pages = block_pages
        self.attn_impl = attn_impl
        self.max_batch_slots = max_batch_slots
        dtype = params["embed"].dtype
        (layers, heads, dim), v_side = cfg.kv_pool_spec  # as EngineCore's
        self.kv = KVCacheManager(
            n_layers=layers, num_pages=num_pages, page_size=page_size,
            n_kv_heads=heads, head_dim=dim, v_side=v_side,
            max_seq_len=max_seq_len, dtype=dtype)
        self._kv_k = self.kv.pool.kv_k
        self._kv_v = self.kv.pool.kv_v
        # Committed tokens whose K/V the draft pool holds, per request.
        self.ctx: dict[str, int] = {}
        # Per-request padded table rows, keyed on the pool's table version:
        # rebuilding the O(max_pages) row every sync wave and draft round
        # is redundant host work unless pages actually moved.
        self._row_cache: dict[str, tuple[int, np.ndarray]] = {}
        # Requests the draft can no longer cover (pool pressure/length):
        # they fall back to prompt-lookup upstream.
        self.dead: set[str] = set()
        self.metrics = {"draft_time_s": 0.0, "draft_tokens": 0,
                        "draft_sync_tokens": 0}

    # ------------------------------------------------------------ lifecycle

    def release(self, request_id: str) -> None:
        self.ctx.pop(request_id, None)
        self.dead.discard(request_id)
        self._row_cache.pop(request_id, None)
        if request_id in self.kv.seqs:
            self.kv.release(request_id)

    def _ensure_pages(self, rid: str, need_tokens: int) -> bool:
        if need_tokens > self.kv.max_pages_per_seq * self.kv.page_size:
            return False
        if rid not in self.kv.seqs:
            self.kv.add_sequence(rid)
            self.ctx[rid] = 0
        if not self.kv.can_extend(rid, need_tokens):
            return False
        self.kv.extend(rid, need_tokens)
        return True

    # ----------------------------------------------------------------- sync

    def _trash_pos(self) -> int:
        return self.kv.max_pages_per_seq * self.kv.page_size

    def _table_row(self, rid: str) -> np.ndarray:
        hit = self._row_cache.get(rid)
        if hit is not None and hit[0] == self.kv.version:
            return hit[1]
        out = np.zeros((self.kv.max_pages_per_seq + 1,), dtype=np.int32)
        out[: self.kv.max_pages_per_seq] = self.kv.page_table_row(rid)
        self._row_cache[rid] = (self.kv.version, out)
        return out

    def _kill(self, rid: str) -> None:
        """Stop covering a request (pool/length pressure): free its pages
        so they serve other drafts; upstream falls back to prompt-lookup."""
        self.dead.add(rid)
        self._row_cache.pop(rid, None)
        if rid in self.kv.seqs:
            self.kv.release(rid)
        self.ctx.pop(rid, None)

    def _sync_batch(self, live: list[tuple[str, list[int]]]) -> None:
        """Write K/V for committed tokens the pool is missing (all but each
        request's last — the decode feed writes that one), in BATCHED
        chunk waves: one [B, chunk] dispatch serves every pending request
        rather than a padded dispatch per request per round."""
        from runbookai_tpu.engine.engine import _prefill_step

        t = self.prefill_chunk
        pending = [(rid, hist) for rid, hist in live
                   if self.ctx.get(rid, 0) < len(hist) - 1]
        while pending:
            rows = pending[: self.max_batch_slots]
            b = self.max_batch_slots  # fixed rows -> one compiled program
            tokens = np.zeros((b, t), dtype=np.int32)
            positions = np.full((b, t), self._trash_pos(), dtype=np.int32)
            tables = np.zeros((b, self.kv.max_pages_per_seq + 1),
                              dtype=np.int32)
            ctx_lens = np.ones((b,), dtype=np.int32)
            for i, (rid, hist) in enumerate(rows):
                start = self.ctx.get(rid, 0)
                chunk = hist[start : min(start + t, len(hist) - 1)]
                tokens[i, : len(chunk)] = chunk
                positions[i, : len(chunk)] = np.arange(start,
                                                       start + len(chunk))
                tables[i] = self._table_row(rid)
                ctx_lens[i] = start + len(chunk)
                self.metrics["draft_sync_tokens"] += len(chunk)
            _, self._kv_k, self._kv_v, _ = _prefill_step(
                self.params, self.cfg, jnp.asarray(tokens), self._kv_k,
                self._kv_v, jnp.asarray(positions), jnp.asarray(tables),
                jnp.asarray(ctx_lens),
                np.zeros((b,), np.int32), jnp.zeros((b,), jnp.int32),
                page_size=self.kv.page_size, block_pages=self.block_pages,
                attn_impl=self.attn_impl,
            )
            for i, (rid, hist) in enumerate(rows):
                self.ctx[rid] = int(ctx_lens[i])
            pending = [(rid, hist) for rid, hist in pending
                       if self.ctx.get(rid, 0) < len(hist) - 1]

    # ---------------------------------------------------------------- draft

    def draft(self, reqs: list[tuple[str, list[int]]], k: int
              ) -> dict[str, list[int]]:
        """Draft up to ``k`` tokens per request with one batched dispatch.

        ``reqs`` pairs request ids with their COMMITTED token history
        (prompt + accepted output). Requests the pool cannot cover return
        no draft (upstream falls back to prompt-lookup).
        """
        from runbookai_tpu.engine.engine import _decode_multi

        t0 = time.perf_counter()
        live: list[tuple[int, str, list[int]]] = []
        for i, (rid, hist) in enumerate(reqs[: self.max_batch_slots]):
            if len(hist) < 1 or rid in self.dead:
                continue
            # Pages for the full committed history + k speculative slots,
            # BEFORE paying any sync dispatch: a request that cannot draft
            # must not sync forever under pool pressure.
            if not self._ensure_pages(rid, len(hist) + k):
                self._kill(rid)
                continue
            live.append((i, rid, hist))
        if not live:
            return {}
        self._sync_batch([(rid, hist) for _, rid, hist in live])

        b = self.max_batch_slots
        tokens = np.zeros((b, 1), dtype=np.int32)
        positions = np.zeros((b, 1), dtype=np.int32)
        ctx_lens = np.zeros((b,), dtype=np.int32)
        tables = np.zeros((b, self.kv.max_pages_per_seq + 1), dtype=np.int32)
        for i, rid, hist in live:
            tokens[i, 0] = hist[-1]
            positions[i, 0] = len(hist) - 1
            ctx_lens[i] = len(hist)
            tables[i] = self._table_row(rid)
        greedy = np.zeros((b,), dtype=np.float32)
        toks, self._kv_k, self._kv_v, _, _ = _decode_multi(
            self.params, self.cfg, jnp.asarray(tokens), jnp.asarray(positions),
            self._kv_k, self._kv_v, jnp.asarray(tables),
            jnp.asarray(ctx_lens), jnp.asarray(greedy),
            jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
            jax.random.PRNGKey(0), jnp.zeros((b,), jnp.int32),
            page_size=self.kv.page_size, block_pages=self.block_pages,
            k_steps=k, attn_impl=self.attn_impl,
        )
        # runbook: noqa[RBK002] — sanctioned sync: one fetch per draft
        # round; the k drafted tokens ride back in a single transfer.
        toks_host = np.asarray(jax.device_get(toks))  # [B, k]
        out: dict[str, list[int]] = {}
        for i, rid, hist in live:
            out[rid] = [int(x) for x in toks_host[i]]
            self.metrics["draft_tokens"] += k
        self.metrics["draft_time_s"] += time.perf_counter() - t0
        return out

"""JAX serving engine: batched prefill + decode with the placement-aware EP
MoE layer, activation-stats collection, and zero-recompile placement
migration (the placement tables are jit arguments; migrating re-gathers the
EP weight slots from the dense master copy — the on-device analogue of the
paper's expert transfer).

The paged step functions thread a device-resident *last-token buffer*
(``last_buf``, ``[max_slots + 1]`` int32 — one entry per serving slot plus
a trailing scratch entry that padding rows read and write) through every
call: the decode argmax is computed on device and scattered back into the
buffer, so the next round's inputs never depend on a host round-trip. The
runtime's zero-stall loop (``ServingRuntime(warmup=True)``) only fetches
the small ``[B]`` sampled-token vector, asynchronously, one round behind.

``warmup_paged`` ahead-of-time compiles the full compaction bucket ladder
(every power-of-two batch width the runtime's ``compact_decode`` /
``compact_prefill`` bucketing can produce) via ``jax.jit(...).lower(...)
.compile()`` with the pool and last-token buffer *donated*, so steady-state
decode re-uses its own KV buffers instead of allocating. Executables are
cached on the engine keyed like ``_paged_fns`` plus the batch width and
origin mode, and ``self.traces`` counts Python traces so a runtime can
assert the hot loop never traces after warmup.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stats import ActivationStats
from repro.models import moe as moe_mod
from repro.models import transformer as tr
from repro.serving.obs import SpanKind, host_span
from repro.serving.sampling import sample_tokens


@dataclasses.dataclass
class ServingEngine:
    rt: tr.Runtime
    params: Any  # EP-layout params (jit arg)
    placement: Any  # stacked EPPlacement [G, ...]
    dense_master: Any = None  # dense expert weights (for migration)
    max_len: int = 256

    def __post_init__(self):
        if self.rt.kv_quant and not self.rt.kv_quant_consistent:
            # serving semantics: prefill attends to the dequantized k/v it
            # stores, so sequential generate(), the dense-pool runtime and
            # paged chunked prefill are all token-identical under int8
            self.rt = dataclasses.replace(self.rt, kv_quant_consistent=True)
        rt = self.rt
        cfg = rt.cfg
        _, self.n_groups = cfg.layer_pattern()
        n_ep = rt.ep_spec.n_ep if rt.ep_spec else 1
        self.stats = ActivationStats(self.n_groups, n_ep, cfg.num_experts)
        self.last_local_frac: float | None = None  # most recent step's
        #   mean local-dispatch fraction (serving-side locality signal)
        self.traces = 0  # Python traces of the serving step fns: the
        #   counter lives in the traced bodies, so compiled executables
        #   (and cache hits) never move it — a zero delta across a serving
        #   run proves the hot loop re-used compiled code throughout

        def _prefill(params, tokens, placement, origin=None):
            self.traces += 1
            return tr.prefill(
                rt,
                params,
                tokens=tokens,
                placement=placement,
                cache_len=self.max_len,
                origin=origin,
            )

        def _decode(
            params, cache, tokens, pos, placement, token_mask=None, origin=None
        ):
            self.traces += 1
            return tr.decode_step(
                rt,
                params,
                cache,
                tokens,
                pos,
                placement,
                token_mask=token_mask,
                origin=origin,
            )

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)

        def _copy_block(pool, src, dst):
            self.traces += 1
            return tr.copy_paged_block(pool, src, dst)

        self._copy_block = jax.jit(_copy_block)
        self._copy_block_raw = _copy_block
        self._paged_fns: dict = {}
        self._paged_raw: dict = {}
        self._compiled: dict = {}  # (kind, block_size, max_pages, B,
        #   tagged) -> AOT executable (kind: "chunk" | "dec"; "copy" is
        #   keyed ("copy", n_blocks, block_size))

    # ------------------------------------------------------------------
    def paged_step_fns(self, block_size: int, max_pages: int):
        """Jitted (prefill_chunk, decode) pair for a paged KV pool. The
        chunk function consumes one block-aligned chunk of *every*
        prefilling slot per call (batched multi-slot prefill). Both thread
        the last-token buffer: ``rows`` maps batch row -> slot index (the
        trailing scratch entry for padding rows), decode gathers its input
        tokens from ``last_buf`` and both scatter their on-device next
        token back into it, so consecutive rounds chain without a host
        transfer. The next token is the seeded Gumbel-max sample of
        ``repro.serving.sampling`` — exact argmax for rows at
        ``temps == 0``, a per-request ``(seed, position)``-keyed draw
        otherwise, so sampling never depends on batch composition.
        The functions specialize on array shapes; the (block_size,
        max_pages) key only keeps one cached pair per pool geometry."""
        key = (block_size, max_pages)
        if key not in self._paged_fns:
            rt = self.rt

            def _chunk(
                params,
                pool,
                last_buf,
                rows,
                tokens,
                page_table,
                write_blocks,
                offset,
                last_idx,
                placement,
                token_mask,
                temps,
                seeds,
                origin=None,
            ):
                self.traces += 1
                logits, pool, mstats = tr.prefill_chunk(
                    rt,
                    params,
                    pool,
                    tokens,
                    page_table,
                    write_blocks,
                    offset,
                    last_idx,
                    placement,
                    token_mask=token_mask,
                    origin=origin,
                )
                # seed the decode chain: rows whose final chunk just landed
                # read their first token from last_buf next round (partial
                # chunks scatter a value no decode round will ever gather).
                # The sample position is the absolute last prompt index.
                with jax.named_scope("head"):
                    first = sample_tokens(
                        logits, temps, seeds, offset + last_idx
                    ).astype(jnp.int32)
                    last_buf = last_buf.at[rows].set(first)
                return last_buf, first, logits, pool, mstats

            def _dec(
                params,
                pool,
                last_buf,
                rows,
                pos,
                page_table,
                placement,
                token_mask,
                temps,
                seeds,
                origin=None,
            ):
                self.traces += 1
                cur = last_buf[rows][:, None]
                logits, pool, mstats = tr.decode_step(
                    rt,
                    params,
                    pool,
                    cur,
                    pos,
                    placement,
                    token_mask=token_mask,
                    page_table=page_table,
                    origin=origin,
                )
                with jax.named_scope("head"):
                    nxt = sample_tokens(logits, temps, seeds, pos).astype(
                        jnp.int32
                    )
                    last_buf = last_buf.at[rows].set(nxt)
                return last_buf, nxt, pool, mstats

            self._paged_fns[key] = (jax.jit(_chunk), jax.jit(_dec))
            self._paged_raw[key] = (_chunk, _dec)
        return self._paged_fns[key]

    # ------------------------------------------------------------------
    def paged_executable(
        self, kind: str, block_size: int, max_pages: int, B: int, tagged: bool
    ):
        """AOT executable for one (step kind, pool geometry, batch width,
        origin mode) point of the warmed ladder, or None when that point
        was not warmed (callers fall back to the lazy jit path)."""
        return self._compiled.get((kind, block_size, max_pages, B, tagged))

    def warmup_paged(
        self,
        *,
        block_size: int,
        max_pages: int,
        max_slots: int,
        pool,
        last_buf,
        origins: str = "both",
    ) -> dict:
        """Ahead-of-time compile the paged serving ladder for one pool
        geometry: every compaction bucket width (powers of two up to
        ``max_slots``, plus ``max_slots`` itself) x {prefill chunk, decode}
        x the requested origin modes, plus the copy-on-write block clone.
        ``pool``/``last_buf`` are the runtime's live buffers — lowering
        only reads their avals; the compiled executables *donate* both, so
        steady-state rounds update the KV pool in place.

        origins: "both" (default), "tagged" (per-request origin arrays) or
        "untagged" — a runtime that knows its stream mode can halve the
        ladder.

        Returns {"seconds": wall_time, "executables": ladder_size} —
        the number of ladder executables this runtime serves from (cached
        entries included). Already-compiled ladder points (same engine,
        same geometry) are skipped, so a second runtime warms for free."""
        t0 = time.perf_counter()
        ladder = 0
        geometry = (block_size, max_pages)
        self.paged_step_fns(*geometry)  # ensure the raw fns exist
        chunk_raw, dec_raw = self._paged_raw[geometry]
        widths = []
        w = 1
        while w < max_slots:
            widths.append(w)
            w <<= 1
        widths.append(max_slots)
        tag_modes = {
            "both": (False, True),
            "tagged": (True,),
            "untagged": (False,),
        }[origins]
        for B in widths:
            rows = jnp.full((B,), max_slots, jnp.int32)
            toks = jnp.zeros((B, block_size), jnp.int32)
            cmask = jnp.zeros((B, block_size), jnp.float32)
            vec = jnp.zeros((B,), jnp.int32)
            tbl = jnp.zeros((B, max_pages), jnp.int32)
            dmask = jnp.zeros((B,), jnp.float32)
            temps = jnp.zeros((B,), jnp.float32)
            seeds = jnp.zeros((B,), jnp.uint32)
            for tagged in tag_modes:
                org = jnp.zeros((B,), jnp.int32) if tagged else None
                key = ("chunk", block_size, max_pages, B, tagged)
                if key not in self._compiled:
                    self._compiled[key] = (
                        jax.jit(chunk_raw, donate_argnums=(1, 2))
                        .lower(
                            self.params,
                            pool,
                            last_buf,
                            rows,
                            toks,
                            tbl,
                            vec,
                            vec,
                            vec,
                            self.placement,
                            cmask,
                            temps,
                            seeds,
                            org,
                        )
                        .compile()
                    )
                ladder += 1
                key = ("dec", block_size, max_pages, B, tagged)
                if key not in self._compiled:
                    self._compiled[key] = (
                        jax.jit(dec_raw, donate_argnums=(1, 2))
                        .lower(
                            self.params,
                            pool,
                            last_buf,
                            rows,
                            vec,
                            tbl,
                            self.placement,
                            dmask,
                            temps,
                            seeds,
                            org,
                        )
                        .compile()
                    )
                ladder += 1
        n_blocks = self._pool_n_blocks(pool)
        key = ("copy", n_blocks, block_size)
        if key not in self._compiled:
            self._compiled[key] = (
                jax.jit(self._copy_block_raw, donate_argnums=0)
                .lower(pool, jnp.int32(0), jnp.int32(0))
                .compile()
            )
        ladder += 1
        return {"seconds": time.perf_counter() - t0, "executables": ladder}

    @staticmethod
    def _pool_n_blocks(pool) -> int:
        """Physical block count of an ``init_paged_cache`` pool (leaf
        layout ``[n_groups, n_blocks, block_size, ...]``)."""
        leaf = jax.tree.leaves(pool)[0]
        return int(leaf.shape[1])

    # ------------------------------------------------------------------
    def copy_block(self, pool, src: int, dst: int):
        """Copy one physical block across every layer of a paged pool —
        the runtime's copy-on-write primitive (clone a shared tail block
        before a sharer's first write). Routed through the warmed donated
        executable when the pool geometry was warmed."""
        leaf = jax.tree.leaves(pool)[0]
        exe = self._compiled.get(
            ("copy", int(leaf.shape[1]), int(leaf.shape[2]))
        )
        fn = exe if exe is not None else self._copy_block
        return fn(pool, jnp.int32(src), jnp.int32(dst))

    # ------------------------------------------------------------------
    def generate(self, tokens: np.ndarray, steps: int = 16, greedy: bool = True):
        """tokens: [B, T] prompt. Returns (generated [B, steps], stats)."""
        B, T = tokens.shape
        assert T + steps <= self.max_len
        logits, cache, mstats = self._prefill(
            self.params, jnp.asarray(tokens), self.placement
        )
        # counts_per_rank are raw token counts: a T-token prefill already
        # carries T x the mass of one decode step, so no extra weighting.
        self._ingest(mstats)
        outs = []
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        local_fracs = []
        for i in range(steps):
            outs.append(cur)
            logits, cache, mstats = self._decode(
                self.params, cache, cur, jnp.int32(T + i), self.placement
            )
            self._ingest(mstats)
            if mstats is not None:
                local_fracs.append(float(mstats["local_frac"].mean()))
            cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        gen = jnp.concatenate(outs, axis=1)
        return np.asarray(gen), {
            "local_frac": float(np.mean(local_fracs)) if local_fracs else 1.0
        }

    def _ingest(self, mstats, weight: float = 1.0):
        """Feed gating statistics to the scheduler-side tracker. ``weight``
        rescales this update's counts (e.g. to down-weight stats from a
        batch containing padding-only rows); it was previously accepted but
        silently ignored."""
        if mstats is None:
            return
        counts = np.asarray(mstats["counts_per_rank"], np.float64) * weight
        self.stats.update(counts)
        if "local_frac" in mstats:
            self.last_local_frac = float(
                np.asarray(mstats["local_frac"]).mean()
            )

    # ------------------------------------------------------------------
    def migrate(self, new_placement_stacked) -> None:
        """Adopt a new placement: re-gather EP expert slots from the dense
        master weights (if available) and swap the tables. No recompile —
        placement tables and weights are both jit arguments, and the AOT
        executables stay valid because the re-gathered arrays keep their
        shapes, dtypes and shardings. One weight is swapped at a time, each
        into a fresh params dict, so the old copy of a weight can be freed
        before the next is gathered: at full width a second EP copy of
        every expert would not fit beside the first and the master."""
        with host_span(SpanKind.MIGRATE):
            self.placement = jax.tree.map(jnp.asarray, new_placement_stacked)
            if self.dense_master is None:
                return
            s2e = self.placement.slot_to_expert
            for k, master in self.dense_master.items():
                if "router" not in master:
                    continue
                for w in ("w1", "w3", "w2"):
                    old = self.params["groups"][k][w]
                    sharding = (
                        old.sharding
                        if isinstance(old.sharding, jax.sharding.NamedSharding)
                        else None
                    )
                    del old
                    new = moe_mod.gather_expert_slots(master[w], s2e, sharding)
                    groups = dict(self.params["groups"])
                    groups[k] = {**groups[k], w: new}
                    self.params = {**self.params, "groups": groups}

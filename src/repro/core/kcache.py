"""Cross-request cache of per-word corpus-distance rows (ISSUE 10).

The paper's core trick is corpus-side reuse of ``K = exp(-lam*M)`` WITHIN
one dispatch (one stacked cdist GEMM per query chunk, ``_compute_kq``);
this module extends the reuse ACROSS dispatches. Real query traffic is
Zipfian over the vocabulary, so the same query words — and therefore the
same ``(V,)`` cdist rows against the frozen corpus vocabulary — recur
constantly between requests. :class:`KCache` keeps the hot words' rows
resident on device in a fixed-capacity slot array with an LRU clock:

- the cache stores the RAW Euclidean distance row ``m[w] = ||vecs - w||``
  per word id, which is independent of ``lam`` and of the solve's
  precision DOMAIN — the linear path derives ``exp(-lam*m)`` and the
  log-domain path ``-lam*m`` elementwise at assembly time
  (:func:`assemble_kq`), so both :class:`SolvePrecision` domains share
  one entry space. The GEMM precision (``fp32`` vs ``bf16``) IS part of
  the cache identity: bf16 operands change ``m`` itself, so a cache is
  built for one ``gemm`` spelling (the engine passes its own).
- miss rows are computed by :func:`_cdist_rows` — the SAME per-element
  reduction as ``_compute_kq``'s stacked GEMM, just ``U`` columns instead
  of ``Q*B``. On the backends this repo targets the per-element dot
  product is bitwise independent of the other output dimensions, so
  cache-on search results are BIT-EXACT against cache-off (pinned by the
  kcache property suite; if a future backend breaks per-row bitwise
  equality the suite's failure is the signal to document a tolerance).
- hot-path dispatch economy (the ROADMAP refusion note): the cached path
  costs a gather + a misses-only GEMM + a scatter instead of one stacked
  GEMM, so on CPU it only wins when enough rows actually hit. The engine
  falls back to the one-shot GEMM below ``kcache_min_hits`` hits — and
  still WARMS the cache from that chunk's ``mq`` (the stacked rows are
  bitwise the rows the cache would have computed).

Shape discipline: every jit here sees pow2-padded operands (unique-id
count, miss count) so serving traffic compiles a bounded executable set,
mirroring the engine's own v_r/Q bucketing. The store carries one extra
SCRATCH row that padded scatter lanes land in and nothing ever reads.

Validity: the cache is keyed against one embedding table by OBJECT
IDENTITY (:attr:`KCache.vecs`). ``append_docs`` grows a corpus without
touching ``vecs`` (``CorpusIndex._replace`` reuses it), so appends are
cache-safe by construction — the engine asserts the identity each staged
chunk and :meth:`KCache.rebind` drops every entry when the table it was
built against is swapped (a different index, a reloaded snapshot).

Not thread-safe: one cache belongs to one engine, whose dispatches are
already serialized (the serving runtime's single worker thread; one
fan-out thread per shard for the sharded engine's per-shard caches).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .sinkhorn import sq_dists


def _pow2(n: int, floor: int = 8) -> int:
    b = max(1, int(floor))
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=("gemm",))
def _cdist_rows(ids: jax.Array, vecs: jax.Array, vecs_sq: jax.Array,
                gemm: str = "fp32") -> jax.Array:
    """(U,) word ids -> (U, V) distance rows against the whole vocabulary.

    Mirrors ``_compute_kq``'s reduction exactly — same operands, same
    ``max(.., 0)`` clamp, same sqrt — with the word axis as the GEMM's N
    dimension, so each output element is the identical dot product the
    stacked chunk GEMM would have produced for that (word, vocab) pair.
    """
    a = jnp.take(vecs, ids, axis=0)                       # (U, w)
    d2 = sq_dists(vecs, a, vecs_sq, gemm_dtype=(
        jnp.bfloat16 if gemm == "bf16" else None))        # (V, U)
    return jnp.sqrt(jnp.maximum(d2, 0.0)).T               # (U, V)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(store, slots, rows):
    """In-place (donated) slot update; padded lanes target the scratch
    row, which is never gathered."""
    return store.at[slots].set(rows)


@jax.jit
def _gather_rows(store, slots):
    return jnp.take(store, slots, axis=0)


@jax.jit
def _extract_rows(mq, qq, bb):
    """Pull per-word rows out of a staged chunk's (Q, V, B) cdist block:
    row for word ``sup[qq[i], bb[i]]`` is ``mq[qq[i], :, bb[i]]``."""
    return mq[qq, :, bb]                                  # (U, V)


@functools.partial(jax.jit, static_argnames=("lam", "log_domain"))
def assemble_kq(rows: jax.Array, inv: jax.Array, mask: jax.Array,
                lam: float, log_domain: bool = False):
    """Cached rows -> the ``(kq, mq)`` pair ``_compute_kq`` returns.

    ``rows`` is (U, V) distance rows, ``inv`` (Q, B) maps each chunk slot
    to its row. The kernel derivation is the same elementwise formula as
    the uncached path (``exp(-lam*m) * mask`` / masked ``-lam*m``), so on
    bitwise-equal ``m`` the pair is bitwise equal too. ``mq`` stays
    unmasked, exactly like the uncached pair — pad slots carry word id
    0's true row and the solve epilogue's ``g > 0`` guard excludes them.
    """
    m = jnp.transpose(jnp.take(rows, inv, axis=0), (0, 2, 1))  # (Q, V, B)
    if log_domain:
        kq = jnp.where(mask[:, None, :] > 0, -lam * m, -jnp.inf)
    else:
        kq = jnp.exp(-lam * m) * mask[:, None, :]
    return kq, m


class KCache:
    """Fixed-capacity device-resident cdist-row cache with an LRU clock.

    ``slots`` bounds device memory at ``(slots + 1) * V`` floats (one
    scratch row). The host side keeps the word->slot map and per-slot
    last-use ticks; all row data stays on device.

    Counters (:meth:`stats`): ``hits``/``misses`` count per-word row
    lookups over ALL traffic (including chunks the engine then served
    via the one-shot fallback — the hit rate is an honest property of
    the traffic, not of the path taken), ``evictions`` counts LRU
    replacements, ``inserts`` rows written, ``lookups`` staged chunks
    seen, ``fallbacks`` chunks served by the one-shot GEMM, ``oversize``
    chunks whose unique-word count exceeded capacity.
    """

    def __init__(self, vecs: jax.Array, vecs_sq: jax.Array, slots: int,
                 gemm: str = "fp32"):
        if slots < 1:
            raise ValueError(f"kcache needs at least 1 slot, got {slots}")
        self.vecs = vecs
        self.vecs_sq = vecs_sq
        self.slots = int(slots)
        self.gemm = gemm
        v = vecs.shape[0]
        self._store = jnp.zeros((self.slots + 1, v), vecs.dtype)
        self._slot_of: dict[int, int] = {}
        self._word_of = np.full(self.slots, -1, np.int64)
        self._last_use = np.zeros(self.slots, np.int64)
        self._tick = 0
        self.reset_counters()

    # ------------------------------------------------------------ queries
    def reset_counters(self) -> None:
        self.hits = self.misses = self.evictions = 0
        self.inserts = self.lookups = self.fallbacks = self.oversize = 0

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {"slots": self.slots, "used": len(self._slot_of),
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "inserts": self.inserts,
                "lookups": self.lookups, "fallbacks": self.fallbacks,
                "oversize": self.oversize,
                "hit_rate": round(self.hits / total, 4) if total else 0.0}

    def lookup(self, ids: np.ndarray) -> int:
        """Count one chunk's unique word ids against the resident set —
        the engine's cached-vs-fallback decision point. Updates the
        hit/miss counters (every chunk's rows are counted exactly once,
        whichever path then serves it) but not the LRU clock."""
        n_hit = sum(1 for w in ids if int(w) in self._slot_of)
        self.lookups += 1
        self.hits += n_hit
        self.misses += len(ids) - n_hit
        return n_hit

    def note_fallback(self, oversize: bool = False) -> None:
        """The engine served a chunk via the one-shot stacked GEMM —
        either below the hit threshold or because the chunk's unique
        words exceed capacity (``oversize``)."""
        self.fallbacks += 1
        if oversize:
            self.oversize += 1

    # ------------------------------------------------------------- slots
    def _claim_slots(self, miss_ids, keep: set) -> np.ndarray:
        """One slot per miss id: free slots first, then LRU victims —
        never a slot holding a word of the CURRENT chunk (``keep``)."""
        out = np.empty(len(miss_ids), np.int64)
        free = np.nonzero(self._word_of < 0)[0]
        n_free = min(free.size, len(miss_ids))
        out[:n_free] = free[:n_free]
        need = len(miss_ids) - n_free
        if need > 0:
            order = np.argsort(self._last_use, kind="stable")
            victims = [s for s in order
                       if self._word_of[s] >= 0
                       and int(self._word_of[s]) not in keep]
            assert len(victims) >= need, "kcache slot accounting broken"
            for j, s in enumerate(victims[:need]):
                del self._slot_of[int(self._word_of[s])]
                self.evictions += 1
                out[n_free + j] = s
        for w, s in zip(miss_ids, out):
            self._slot_of[int(w)] = int(s)
            self._word_of[s] = int(w)
        return out

    def _insert(self, miss_ids, rows_padded, pad_to: int,
                keep: set) -> None:
        """Scatter ``len(miss_ids)`` freshly computed rows (carried in a
        ``pad_to``-long device batch; surplus lanes hit the scratch
        row). ``keep`` is the CURRENT chunk's word set — its slots are
        exempt from LRU eviction while the chunk is being staged."""
        slots = self._claim_slots(miss_ids, keep)
        target = np.full(pad_to, self.slots, np.int32)   # scratch row
        target[:len(miss_ids)] = slots
        self._store = _scatter_rows(self._store, jnp.asarray(target),
                                    rows_padded)
        self._last_use[slots] = self._tick
        self.inserts += len(miss_ids)

    # -------------------------------------------------------------- rows
    def rows(self, ids: np.ndarray) -> jax.Array:
        """(U,) sorted unique word ids -> (U_pad, V) resident rows (tail
        lanes repeat the last id — callers index through ``ids`` order,
        so the padding is inert). Misses are computed by the uncached
        reduction and inserted; every id's slot is touched on the LRU
        clock. Counters are :meth:`lookup`'s job — call it first."""
        assert len(ids) <= self.slots, "caller must fall back on oversize"
        self._tick += 1
        miss = [int(w) for w in ids if int(w) not in self._slot_of]
        # touch hits BEFORE claiming miss slots so this chunk's own rows
        # are never the LRU victims of its own misses
        hit_slots = [self._slot_of[int(w)] for w in ids
                     if int(w) in self._slot_of]
        if hit_slots:
            self._last_use[np.asarray(hit_slots)] = self._tick
        if miss:
            pad = _pow2(len(miss))
            padded = np.zeros(pad, np.int32)
            padded[:len(miss)] = miss
            fresh = _cdist_rows(jnp.asarray(padded), self.vecs,
                                self.vecs_sq, gemm=self.gemm)
            self._insert(miss, fresh, pad,
                         keep=set(int(w) for w in ids))
        u_pad = _pow2(len(ids))
        slot_idx = np.full(u_pad, self._slot_of[int(ids[-1])], np.int32)
        slot_idx[:len(ids)] = [self._slot_of[int(w)] for w in ids]
        return _gather_rows(self._store, jnp.asarray(slot_idx))

    def warm(self, sup_np: np.ndarray, mq: jax.Array) -> None:
        """Insert a fallback chunk's rows from its already-computed
        ``(Q, V, B)`` cdist block — bitwise the rows :meth:`rows` would
        have produced, at the cost of one small gather instead of a
        GEMM. Oversize chunks only warm as many rows as fit."""
        self._tick += 1
        flat = sup_np.reshape(-1)
        ids, first = np.unique(flat, return_index=True)
        fresh = [(int(w), int(f)) for w, f in zip(ids, first)
                 if int(w) not in self._slot_of]
        # refresh resident rows' clock even on the fallback path — they
        # were just used by this chunk
        hit_slots = [self._slot_of[int(w)] for w in ids
                     if int(w) in self._slot_of]
        if hit_slots:
            self._last_use[np.asarray(hit_slots)] = self._tick
        # warming never EVICTS: a cold chunk's rows must not displace the
        # hot resident set the LRU clock is protecting — only free slots
        # are filled
        room = self.slots - len(self._slot_of)
        if room <= 0 or not fresh:
            return
        fresh = fresh[:room]
        pad = _pow2(len(fresh))
        qq = np.zeros(pad, np.int32)
        bb = np.zeros(pad, np.int32)
        b = sup_np.shape[1]
        for j, (_, f) in enumerate(fresh):
            qq[j], bb[j] = f // b, f % b
        rows = _extract_rows(mq, jnp.asarray(qq), jnp.asarray(bb))
        self._insert([w for w, _ in fresh], rows, pad,
                     keep=set(int(w) for w in ids))

    # ----------------------------------------------------------- validity
    def rebind(self, vecs: jax.Array, vecs_sq: jax.Array) -> "KCache":
        """The embedding table this cache was built against is gone —
        drop every entry and bind to the new one (counters survive: a
        rebind is an operational event worth seeing in the hit rate)."""
        fresh = KCache(vecs, vecs_sq, self.slots, gemm=self.gemm)
        for k in ("hits", "misses", "evictions", "inserts", "lookups",
                  "fallbacks", "oversize"):
            setattr(fresh, k, getattr(self, k))
        return fresh

"""Slot-based, capacity-bucketed KV cache for continuous-batching decode.

The decode inner loop must be ONE compiled, shape-stable program that
stays resident across requests (the Julia->TPU full-compilation lesson,
PAPERS.md): every tensor the step touches therefore has a fixed shape.
This cache provides that shape discipline:

* **Slots** — the cache is a fixed ``(S, heads * d, L)`` buffer per
  layer, ``S = max_slots``.  A sequence owns one slot row for its whole
  lifetime; admission writes its prefilled keys/values into the row,
  retirement simply frees the slot id (no copy, no compaction — the
  row's stale contents are masked off by the per-slot position mask).
* **Positions last** — ``L`` is the minor axis because that is the
  order the device stores the buffer in and the decode attention reads
  it in.  A TPU keeps an array in the tile-compact layout of its
  shape (8 x 128 tiles over the two minor axes), so the former
  ``(S, L, heads, d)`` with ``d`` = 64 was stored ``[S][heads][d][L]``
  anyway, while the step's scatter wanted ``[S][L][heads][d]``: XLA
  relayouted every layer's whole K and V buffer on the way into and
  out of every token (two thirds of a decode step, PERF.md PR 27).
  With ``(S, heads * d, L)`` the row-major layout of the logical shape
  IS the stored one (channels on sublanes, ``L`` on lanes, nothing
  padded for ``L`` a multiple of 128), and the step writes each
  slot's column in place, so the donated buffers alias its outputs
  with no copy: the hybrid and the sparse-expert family with ONE
  kernel call a layer's K and V over all slots
  (``ops.pallas.column_write``, PR 33: the 128-position tile column
  that holds the slot's position is read, one lane of it replaced,
  and written back), the GPT family's step
  (``model._slot_block_step``) still with one
  ``dynamic_update_slice`` a slot and buffer, ~3.5 us each whatever
  it moves.  Heads and ``d`` share ONE axis: a column is then whole
  sublane tiles of one 2-D block, and the per-slot updates compile to
  half the code; the step's view ``(S, heads, d, L)`` of it is free.
  Callers keep handing :meth:`PagedKVCache.write_prompt` plain
  ``(Lp, heads, d)`` rows.
* **Capacity buckets** — ``L`` is drawn from a power-of-two-style grid
  (``MXNET_GEN_KV_BUCKETS``).  The decode step compiles once per
  bucket; when any live sequence needs a position ``>= L`` the whole
  cache pads up to the next bucket (`grow`), switching the engine to
  that bucket's pre-compiled step.  Steady-state traffic confined to
  the warmed grid therefore triggers ZERO XLA compiles.
* **Donation-friendly** — the engine replaces the layer buffers with
  the decode step's outputs each iteration, so XLA can update the
  cache in place (the buffers are donated to the compiled step).

* **Kinds of per-slot state** — a layer's ``kind`` says what a slot
  holds of it: ``rows`` (the above: K/V rows that grow, in the bucket
  grid), ``window`` (K/V rows capped at the attention window,
  ``(S, heads * d, window)`` whatever the bucket, used as a ring by
  the family's step: position ``p`` in column ``p % window``, never
  unrolled, because softmax does not care for the order of its keys;
  that holds without a position embedding and also for keys stored
  already rotated at their absolute position), ``state`` (arrays of
  fixed shape, float32: a recurrence's state) or ``none``.  The GPT
  family is ``rows`` in every layer; ``serving.hybrid`` mixes all
  four, ``serving.moe`` three rings of 4096 and one ``rows`` a period.
  One slot table
  serves them all: admission installs every kind for its slot
  (:meth:`PagedKVCache.write_prompt`), only ``rows`` grow.

Positions/occupancy are host-side numpy bookkeeping: the device only
ever sees the fixed-shape buffers plus an ``(S,)`` position vector.
"""
from __future__ import annotations

import collections
import hashlib
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError, getenv, register_env
from .. import metrics as _metrics
from .. import tracing as _tracing

__all__ = ["PagedKVCache", "PrefixCache", "kv_bucket_grid",
           "round_up_bucket"]

register_env("MXNET_GEN_KV_BUCKETS", "128,256,512,1024",
             "KV-cache capacity bucket grid for the generation engine "
             "(comma list of padded sequence lengths). The resident "
             "decode step compiles once per bucket; a sequence whose "
             "prompt+new-tokens budget exceeds the top bucket is "
             "rejected at submit.")
register_env("MXNET_GEN_PREFIX_CACHE_SLOTS", 8,
             "Resident entries in the generation engine's shared-prefix "
             "KV cache: bucket-aligned prompt prefixes (e.g. a common "
             "system prompt) keep their K/V rows on the device and "
             "admissions COPY them into the slot instead of re-running "
             "prefill, collapsing TTFT for the dominant traffic class. "
             "LRU eviction past this bound; 0 disables prefix caching.")


def kv_bucket_grid(buckets: Optional[Sequence[int]] = None
                   ) -> Tuple[int, ...]:
    """The configured KV capacity grid, sorted ascending."""
    if buckets is None:
        raw = str(getenv("MXNET_GEN_KV_BUCKETS", "128,256,512,1024"))
        buckets = [int(b) for b in raw.split(",") if b.strip()]
    out = tuple(sorted({int(b) for b in buckets}))
    if not out or out[0] < 1:
        raise MXNetError(f"bad KV bucket grid {buckets!r}")
    return out


def round_up_bucket(n: int, grid: Sequence[int]) -> int:
    """Smallest grid bucket >= n (raises past the top — an unbounded
    length would reopen the compile hole the grid exists to close)."""
    for b in grid:
        if b >= n:
            return b
    raise MXNetError(
        f"required capacity {n} exceeds the top KV bucket {grid[-1]}; "
        "reject the request (or raise MXNET_GEN_KV_BUCKETS)")


KINDS = ("rows", "window", "state", "none")


class PagedKVCache:
    """Per-layer ``(max_slots, heads * head_dim, L)`` K/V buffers plus
    host-side slot bookkeeping.

    ``layers`` buffers live as jax arrays (device-resident); ``k(i)`` /
    ``v(i)`` hand them to the decode step and :meth:`replace` swaps in
    the step's outputs (donation-compatible).

    ``kinds`` names each layer's kind of per-slot state (module
    docstring; default: ``rows`` everywhere).  ``k(i)`` / ``v(i)``
    count the ``rows`` layers; the ``window`` layers' K and V rings of
    ``window`` rows and the ``state`` layers' arrays (``state_shapes``:
    {name: shape behind the slot axis}, one of each a layer) are the
    lists of :attr:`state`.

    ``stacked`` puts the ``rows`` layers on a leading axis of ONE K and
    ONE V buffer ``(layers, max_slots, heads * head_dim, L)``: what a
    family whose programs loop over its layers indexes from inside the
    loop (``serving.loop``: an entry a (loop step, layer)).  Admission
    then takes one ``(layers, Lp, heads, d)`` array a side.
    """

    def __init__(self, n_layers: int, n_heads: int, head_dim: int,
                 max_slots: int,
                 buckets: Optional[Sequence[int]] = None,
                 dtype: Any = None,
                 prefix_slots: Optional[int] = None,
                 prefix: Optional["PrefixCache"] = None,
                 kinds: Optional[Sequence[str]] = None,
                 window: int = 0,
                 state_shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
                 stacked: bool = False) -> None:
        import jax
        import jax.numpy as jnp
        self.grid = kv_bucket_grid(buckets)
        self.n_layers = int(n_layers)
        self.kinds = tuple(kinds) if kinds is not None \
            else ("rows",) * self.n_layers
        if len(self.kinds) != self.n_layers \
                or not set(self.kinds) <= set(KINDS):
            raise MXNetError(
                f"kinds must name one of {KINDS} for each of the "
                f"{self.n_layers} layers, got {self.kinds!r}")
        self.n_rows = self.kinds.count("rows")
        self.n_window = self.kinds.count("window")
        self.n_state = self.kinds.count("state")
        # the rows' leading axes: none (a buffer a ``rows`` layer), or
        # the layers themselves (``stacked``: ONE K and one V buffer)
        self._lead = (self.n_rows,) if stacked else ()
        self.window = int(window)
        self.state_shapes = {name: tuple(int(d) for d in shape)
                             for name, shape in (state_shapes or {}).items()}
        if (self.n_window and self.window < 1) \
                or (self.n_state and not self.state_shapes):
            raise MXNetError(
                "window layers need window >= 1 and state layers "
                "their state_shapes")
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.max_slots = int(max_slots)
        if self.max_slots < 1:
            raise MXNetError(f"max_slots must be >= 1, got {max_slots}")
        self.dtype = jnp.dtype(dtype) if dtype is not None \
            else jnp.float32
        self.bucket = self.grid[0]
        # bytes one slot holds of each kind: a row (K and V, every
        # ``rows`` layer), a window row, the whole (float32) state
        row = 2 * self.n_heads * self.head_dim \
            * _np.dtype(self.dtype).itemsize
        self._slot_bytes = {
            "rows": self.n_rows * row, "window": self.n_window * row,
            "state": 4 * self.n_state * sum(
                int(_np.prod(s)) for s in self.state_shapes.values())}
        # the one device every buffer here is committed to, and every
        # host operand of a step over them (DecodeModel.dispatch): a
        # jitted call keys its executable on whether and where its
        # inputs are committed, so the choice is made once, here
        self.device = jax.local_devices()[0]
        self._k: List[Any] = []
        self._v: List[Any] = []
        # the fixed-size kinds, {name: one buffer a layer of the kind}
        self.state: Dict[str, List[Any]] = {}
        self._alloc_buffers(self.bucket)
        self._alloc_fixed()
        # host bookkeeping: next write position per slot (== tokens
        # resident in the row once the steps launched have run: the
        # engine advances it when it LAUNCHES a step, not when it reads
        # the token back), -1 marks a free slot
        self.positions = _np.full((self.max_slots,), -1, _np.int64)
        # the pinned shared-prefix region: hot prompt-prefix K/V rows
        # resident beside the slot buffers, copied (never re-prefilled)
        # into slots at admission.  Pass ``prefix`` to SHARE one store
        # across engines (replicas on one device hit each other's
        # inserts — a resurrected sequence lands on a warm prefix)
        self.prefix = prefix if prefix is not None \
            else PrefixCache(prefix_slots)
        _metrics.GEN_KV_BUCKET_LEN.set(self.bucket)

    # -- buffers ------------------------------------------------------------
    def _alloc_buffers(self, L: int) -> None:
        import jax
        shape = self._lead + (self.max_slots,
                              self.n_heads * self.head_dim, L)
        n = 1 if self._lead else self.n_rows
        held = self._k + self._v
        if len(held) == 2 * n and all(
                b.shape == shape and not b.is_deleted() for b in held):
            # already at this bucket and alive (warm-up walks a grid of
            # one bucket several times): kept as they are.  What a slot
            # held before stays invisible behind its position, as after
            # a retirement; stacked rows are 8 GB to zero and upload
            return
        # what is held goes before what replaces it is made: stacked
        # rows are most of a chip
        self._k = self._v = []
        # device_put COMMITS the buffers: a jitted call keys its cache
        # on input committed-ness, so fresh uncommitted zeros would
        # make the first post-reset admission recompile the row write
        # even at an identical shape.  HOST zeros, not jnp.zeros: an
        # eager jnp.zeros compiles a tiny program per shape — a pure
        # transfer keeps restart warmup (which walks every bucket
        # shape) at zero XLA compiles
        zeros = _np.zeros(shape, self.dtype)
        self._k = [jax.device_put(zeros, self.device) for _ in range(n)]
        self._v = [jax.device_put(zeros, self.device) for _ in range(n)]
        _metrics.GEN_CACHE_BYTES.labels(kind="rows").set(
            self.bytes_by_kind()["rows"])

    def _fixed_shapes(self) -> Dict[str, Tuple[int, Tuple[int, ...], Any]]:
        """{name: (buffers, shape behind the slot axis, dtype)} of the
        kinds whose size does not follow the bucket."""
        shapes: Dict[str, Tuple[int, Tuple[int, ...], Any]] = {}
        if self.n_window:
            ring = (self.n_heads * self.head_dim, self.window)
            shapes["wk"] = shapes["wv"] = (self.n_window, ring, self.dtype)
        if self.n_state:
            for name, shape in self.state_shapes.items():
                shapes[name] = (self.n_state, shape, _np.float32)
        return shapes

    def _fixed_zeros(self, lead: Tuple[int, ...]) -> Dict[str, List[Any]]:
        """The fixed-size kinds zeroed, ``lead`` before each shape
        (host zeros, committed: see ``_alloc_buffers``)."""
        import jax
        return {name: [jax.device_put(_np.zeros(lead + shape, dtype),
                                      self.device)
                       for _ in range(n)]
                for name, (n, shape, dtype) in self._fixed_shapes().items()}

    def _alloc_fixed(self) -> None:
        """(Re)allocate the ``window`` and ``state`` buffers."""
        self.state = self._fixed_zeros((self.max_slots,))
        allocated = self.bytes_by_kind()
        for kind in ("window", "state"):
            _metrics.GEN_CACHE_BYTES.labels(kind=kind).set(allocated[kind])

    def bytes_by_kind(self) -> Dict[str, int]:
        """Device bytes allocated, by kind."""
        per = self._slot_bytes
        return {"rows": int(self.max_slots * self.bucket * per["rows"]),
                "window": int(self.max_slots * self.window * per["window"]),
                "state": int(self.max_slots * per["state"])}

    def live_bytes_by_kind(self) -> Dict[str, int]:
        """Of :meth:`bytes_by_kind`, what the live slots' sequences
        hold: their resident rows, at most ``window`` of them in the
        window layers, and their state."""
        per = self._slot_bytes
        live = self.positions[self.positions >= 0]
        return {"rows": int(live.sum() * per["rows"]),
                "window": int(_np.minimum(live, self.window).sum()
                              * per["window"]),
                "state": int(live.size * per["state"])}

    def publish_live_bytes(self) -> None:
        for kind, n in self.live_bytes_by_kind().items():
            _metrics.GEN_CACHE_LIVE_BYTES.labels(kind=kind).set(n)

    def k(self, layer: int) -> Any:
        return self._k[0][layer] if self._lead else self._k[layer]

    def v(self, layer: int) -> Any:
        return self._v[0][layer] if self._lead else self._v[layer]

    def layers(self) -> List[Tuple[Any, Any]]:
        return list(zip(self._k, self._v))

    def buffers(self) -> Tuple[Any, ...]:
        """What the decode step consumes (donated) and :meth:`replace`
        takes back: the rows' K and V lists and, where the cache holds
        them, the fixed-size kinds."""
        return (self._k, self._v, self.state) if self.state \
            else (self._k, self._v)

    def replace(self, new_k: Sequence[Any], new_v: Sequence[Any],
                state: Optional[Dict[str, List[Any]]] = None) -> None:
        """Swap in the decode step's updated buffers (the old ones were
        donated to the compiled call)."""
        self._k = list(new_k)
        self._v = list(new_v)
        if state is not None:
            self.state = state

    # -- slots --------------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i in range(self.max_slots)
                if self.positions[i] < 0]

    def occupancy(self) -> int:
        return int((self.positions >= 0).sum())

    def alloc(self) -> Optional[int]:
        for i in range(self.max_slots):
            if self.positions[i] < 0:
                self.positions[i] = 0
                return i
        return None

    def free(self, slot: int) -> None:
        self.positions[slot] = -1

    # -- admission write ----------------------------------------------------
    def write_prompt(self, slot: int, ks: Sequence[Any],
                     vs: Sequence[Any], t0: int,
                     start: int = 0,
                     state: Optional[Dict[str, List[Any]]] = None
                     ) -> None:
        """Install prefilled rows into ``slot``: ``ks[l]``/``vs[l]``
        are ``(Lp, heads, d)`` (one array ``(layers, Lp, heads, d)`` each
        where the rows are stacked; padded to a length bucket; the pad rows
        carry garbage KV that stays masked until the decode loop
        overwrites them position by position).  ``start`` places the
        rows at positions ``start..start+Lp`` — the shared-prefix
        admission writes the copied prefix at 0 and the suffix prefill
        at the prefix length (``start`` is a traced operand, so every
        offset shares one compiled write per shape pair).  ``t0`` is
        the slot's resident-token count after the write.  Grows the
        cache first if the rows exceed the current bucket.

        ``state`` holds the slot's fixed-size kinds at position ``t0``
        in the layout of :attr:`state` without the slot axis (the
        window rings, the recurrence's arrays).  It REPLACES whatever
        the slot's last owner left, whole, so nothing of a freed
        slot's request reaches the next one."""
        if bool(self.state) != (state is not None):
            raise MXNetError(
                f"a slot of this cache holds rows and "
                f"{sorted(self.state) or 'nothing else'}; an admission "
                "has to install exactly that")
        Lp = int(ks[0].shape[-3])
        with _tracing.child_span("kv.write_prompt", rows=Lp,
                                 bucket=self.bucket):
            if int(start) + Lp > self.bucket:
                self.grow(round_up_bucket(int(start) + Lp, self.grid))
            # ONE dispatch writes every layer's K and V row: per-call
            # dispatch overhead is what dominates a row copy on small
            # hosts, so 2L separate writes would bury the prefix
            # cache's TTFT win under launch latency.  The buffers are
            # donated and come back written in place
            self._write_rows(list(ks) + list(vs), slot, start)
        if state is not None:
            with _tracing.child_span("cache.install_state", slot=int(slot),
                                     rows=min(int(t0), self.window)):
                self.state = _install_state_jit(self.state, state,
                                                _np.int32(slot))
            _metrics.GEN_STATE_INSTALLS_TOTAL.inc()
        self.positions[slot] = int(t0)

    def _write_rows(self, rows: List[Any], slot: int, start: int) -> None:
        out = _write_rows_jit(self._k + self._v, rows, _np.int32(slot),
                              _np.int32(start))
        self._k, self._v = out[:len(out) // 2], out[len(out) // 2:]

    # -- rollback -----------------------------------------------------------
    def truncate(self, slot: int, position: int) -> int:
        """Roll ``slot`` back so ``position`` is its next write index,
        discarding every row at ``position..`` — the speculative-decode
        rejection path.  No device work happens: rows past a slot's
        position are already invisible to the decode/verify attention
        mask, so rolling back is pure host bookkeeping and the next
        accepted token's write makes the row bit-identical to one that
        was never speculated into (CI pins this).  Only ever touches
        the slot's own rows — shared-prefix entries hold their own
        buffers (admission COPIES them in), so a rollback can never
        corrupt a refcounted prefix.  Returns the number of rows
        discarded."""
        if not 0 <= int(slot) < self.max_slots:
            raise MXNetError(
                f"truncate: slot {slot} out of range "
                f"(max_slots={self.max_slots})")
        cur = int(self.positions[slot])
        if cur < 0:
            raise MXNetError(f"truncate: slot {slot} is free")
        position = int(position)
        if position < 0 or position > cur:
            raise MXNetError(
                f"truncate: position {position} outside the slot's "
                f"resident range [0, {cur}] — rollback only ever "
                "rewinds (forward motion is the decode loop's job)")
        dropped = cur - position
        if dropped:
            self.positions[slot] = position
            _metrics.GEN_KV_ROLLBACKS_TOTAL.inc()
        return dropped

    # -- capacity -----------------------------------------------------------
    def needed_capacity(self) -> int:
        """Positions the next decode step will write: max live position
        + 1 (0 when idle)."""
        live = self.positions[self.positions >= 0]
        return int(live.max()) + 1 if live.size else 0

    def ensure_capacity(self, pos_needed: int) -> bool:
        """Grow to the bucket covering ``pos_needed`` write positions;
        returns True when a migration happened."""
        if pos_needed <= self.bucket:
            return False
        self.grow(round_up_bucket(pos_needed, self.grid))
        return True

    def grow(self, new_bucket: int) -> None:
        if new_bucket <= self.bucket:
            return
        self._k = [_grow_rows(k, new_bucket) for k in self._k]
        self._v = [_grow_rows(v, new_bucket) for v in self._v]
        self.bucket = new_bucket
        _metrics.GEN_KV_MIGRATIONS_TOTAL.inc()
        _metrics.GEN_KV_BUCKET_LEN.set(new_bucket)
        _metrics.GEN_CACHE_BYTES.labels(kind="rows").set(
            self.bytes_by_kind()["rows"])

    def warmup_writes(self, prompt_buckets: Sequence[int]) -> int:
        """Pre-compile every admission/migration executable: the
        prompt-row write per (capacity bucket x prompt bucket) pair,
        the grow pad per (bucket -> larger bucket) pair, and the
        prefix-row shrink per (prompt bucket -> smaller prompt bucket)
        pair — so steady-state traffic never compiles them."""
        import jax
        dev = self.device
        n = 0
        for i, L in enumerate(self.grid):
            self.bucket = int(L)
            self._alloc_buffers(self.bucket)
            for Lp in prompt_buckets:
                if Lp > L:
                    continue
                rows = [jax.device_put(
                    _np.zeros(self._lead + (int(Lp), self.n_heads,
                                            self.head_dim), self.dtype),
                    dev) for _ in range(2 * len(self._k))]
                # one fused write covers every layer's K and V; zeros
                # into zeros is a no-op in content
                self._write_rows(rows, 0, 0)
                n += 1
            for L2 in self.grid[i + 1:]:
                # live migrations may leap buckets (a long-prompt
                # admission), so warm every ordered pair
                _grow_rows(self._k[0], int(L2))
                n += 1
        if self.prefix.slots > 0:
            # prefix insertion slices a prefill's (Lp, h, d) rows down
            # to the bucket-aligned prefix length: warm each ordered
            # (larger -> smaller) prompt-bucket pair
            pbs = sorted(int(b) for b in prompt_buckets)
            for i, Lp in enumerate(pbs):
                rows = [jax.device_put(
                    _np.zeros((Lp, self.n_heads, self.head_dim),
                              self.dtype), dev)
                    for _ in range(2 * self.n_rows)]
                for Pb in pbs[:i]:
                    _shrink_rows(rows, Pb)
                    n += 1
        if self.state:
            # the fixed-size kinds have one shape whatever the bucket
            # and the prompt: one install program
            self.state = _install_state_jit(
                self.state, self._fixed_zeros(()), _np.int32(0))
            n += 1
        self.bucket = self.grid[0]
        self._alloc_buffers(self.bucket)
        return n

    def reset_buffers(self) -> None:
        """Reallocate the K/V buffers at the current bucket.  Needed
        after a decode-step FAILURE: the step consumed the old buffers
        by donation, so a raise after dispatch leaves ``_k``/``_v``
        pointing at deleted arrays — without this, every later
        admission would fail on them forever."""
        self._alloc_buffers(self.bucket)
        self._alloc_fixed()

    def reset_if_empty(self) -> None:
        """Shrink back to the smallest bucket once no sequence is live
        (only then: shrinking under live traffic would thrash)."""
        if self.occupancy() == 0 and self.bucket != self.grid[0]:
            self.bucket = self.grid[0]
            self._alloc_buffers(self.bucket)
            _metrics.GEN_KV_BUCKET_LEN.set(self.bucket)

    def describe(self) -> dict:
        return {
            "max_slots": self.max_slots,
            "bucket": self.bucket,
            "buckets": list(self.grid),
            "occupancy": self.occupancy(),
            "layers": self.n_layers,
            "kinds": {k: self.kinds.count(k) for k in KINDS
                      if k in self.kinds},
            "window": self.window,
            "bytes": self.bytes_by_kind(),
            "heads": self.n_heads,
            "head_dim": self.head_dim,
            "dtype": str(self.dtype),
            # axis order of every resident buffer (module docstring)
            "layout": ("(layers, " if self._lead else "(")
            + "max_slots, heads*head_dim, bucket)",
            # where the buffers actually live, not where they were asked
            "platforms": sorted({
                d.platform
                for b in self._k + self._v + sum(self.state.values(), [])
                for d in b.devices()}),
            "prefix_cache": self.prefix.describe(),
        }


# jitted helpers — one executable per (cache shape, prompt shape) pair,
# all drawn from the bucket grid (warmable, bounded).

def _grow_rows(buf: Any, new_len: int) -> Any:
    fn = _grow_jits.get(int(new_len))
    if fn is None:
        import jax
        import jax.numpy as jnp

        def grow(b, _L=int(new_len)):
            with jax.named_scope("cache/grow"):
                return jnp.pad(b, ((0, 0),) * (b.ndim - 1)
                               + ((0, _L - b.shape[-1]),))

        fn = _grow_jits[int(new_len)] = _tracing.program(
            grow, "cache_resize", attrs={"rows": int(new_len)})
    return fn(buf)


_grow_jits: dict = {}


def _make_write_rows():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def write(bufs, rows, slot, start):
        # bufs: every layer's K then V buffer (S, h * d, L), or the one
        # stacked K and V (layers, S, h * d, L); rows: the matching
        # (Lp, h, d) rows, layers before them where stacked, turned to
        # (h * d, Lp) here (small: one prompt's rows); slot/start
        # scalars: place each row-set at [slot, :, start:start+Lp] in
        # ONE executable (per-dispatch overhead dominates a row copy,
        # so one call per layer per K/V would bury the admission in
        # launch latency).  start is a traced operand (prefix copies
        # write at 0, suffix prefills at the prefix length) so every
        # offset shares this one executable per shape pair.  The
        # buffers are DONATED and written in place: an un-donated write
        # holds the cache twice while it runs, and stacked rows are
        # most of a chip
        def place(b, r):
            r = r.reshape(r.shape[:-2] + (-1,)).swapaxes(-1, -2)
            lead = (_np.int32(0),) * (b.ndim - 3)
            return lax.dynamic_update_slice(
                b, jnp.expand_dims(r, -3).astype(b.dtype),
                lead + (slot, _np.int32(0), start))
        with jax.named_scope("cache/write"):
            return [place(b, r) for b, r in zip(bufs, rows)]
    return _tracing.program(write, "cache_write", donate_argnums=(0,))


class _Lazy:
    """Defer the jax import to first use (the serving package must stay
    importable without touching the backend)."""

    def __init__(self, make) -> None:
        self._make, self._fn = make, None

    def __call__(self, *args):
        if self._fn is None:
            self._fn = self._make()
        return self._fn(*args)


_write_rows_jit = _Lazy(_make_write_rows)


def _make_install_state():
    import jax
    from jax import lax

    def install(bufs, new, slot):
        # bufs: {name: [(S, ...) a layer]}; new: the same without the
        # slot axis.  The whole of the slot is replaced.  DONATED: the
        # window rings are as large as a bucket's rows and an
        # un-donated write would hold them twice
        with jax.named_scope("cache/write"):
            return jax.tree_util.tree_map(
                lambda b, r: lax.dynamic_update_slice(
                    b, r[None].astype(b.dtype), (slot,) + (0,) * r.ndim),
                bufs, new)
    return _tracing.program(install, "cache_install", donate_argnums=(0,))


_install_state_jit = _Lazy(_make_install_state)


def _shrink_rows(rows: List[Any], new_len: int) -> List[Any]:
    """Slice every (Lp, h, d) row-set in ``rows`` down to its first
    ``new_len`` rows in ONE executable — the prefix-insertion path (a
    prefill's K and V rows cut to the bucket-aligned prefix).  One
    executable per (Lp, new_len) pair, all drawn from the
    prompt-bucket grid (warmable, bounded)."""
    fn = _shrink_jits.get(int(new_len))
    if fn is None:
        import jax

        def shrink(bs, _n=int(new_len)):
            with jax.named_scope("cache/grow"):
                return [b[:_n] for b in bs]

        fn = _shrink_jits[int(new_len)] = _tracing.program(
            shrink, "cache_resize", attrs={"rows": int(new_len)})
    return fn(list(rows))


_shrink_jits: dict = {}


# ---------------------------------------------------------------------------
# shared-prefix KV cache (the pinned region)
# ---------------------------------------------------------------------------

class _PrefixEntry:
    """One resident prefix: per-layer K/V rows (Pb, heads, d) on the
    device, the real prefix length ``q`` (rows past it are pad
    garbage, masked by slot positions like any admission), and — when
    the prefix IS a whole prompt — the prefill's last-token logits, so
    an identical-prompt admission emits its first token without any
    model call."""

    __slots__ = ("key", "ks", "vs", "q", "bucket", "logits", "refs")

    def __init__(self, key: str, ks: List[Any], vs: List[Any], q: int,
                 logits: Optional[_np.ndarray]) -> None:
        self.key = key
        self.ks = ks
        self.vs = vs
        self.q = int(q)
        self.bucket = int(ks[0].shape[0])
        self.logits = logits
        self.refs = 0


def prefix_key(tokens: _np.ndarray, q: int) -> str:
    """Content hash of the first ``q`` tokens (int32-canonical)."""
    raw = _np.ascontiguousarray(
        _np.asarray(tokens, _np.int32)[:q]).tobytes()
    return f"{q}:{hashlib.sha1(raw).hexdigest()}"


class PrefixCache:
    """Ref-counted, LRU-bounded store of hot prompt-prefix K/V rows.

    One store may be SHARED by engines serving the same
    :class:`~mxnet_tpu.serving.model.DecodeModel` (replicas on one
    device — ``tools/serve.py`` does this) so any replica's cold
    prefill warms them all; entries are model-specific, so never share
    a store across different models/weights.

    Engine threads probe/pin/insert concurrently under the shared
    store, so every method is lock-guarded; nothing under the lock
    touches the device (entries hold already-built arrays — eviction
    just drops the references).  ``refs`` counts admissions currently
    copying from the entry: eviction only ever removes unreferenced
    entries, so rows cannot vanish out from under an admission on a
    sibling engine."""

    def __init__(self, slots: Optional[int] = None) -> None:
        if slots is None:
            slots = int(getenv("MXNET_GEN_PREFIX_CACHE_SLOTS", 8))
        self.slots = max(0, int(slots))
        self._entries: "collections.OrderedDict[str, _PrefixEntry]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def lookup(self, key: str, pin: bool = False
               ) -> Optional[_PrefixEntry]:
        """The entry for ``key`` (refreshing recency), or None.
        ``pin=True`` bumps the refcount — pair with :meth:`unpin`."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return None
            self._entries.move_to_end(key)
            if pin:
                e.refs += 1
            return e

    def unpin(self, key: str) -> None:
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e.refs > 0:
                e.refs -= 1

    def insert(self, key: str, ks: List[Any], vs: List[Any], q: int,
               logits: Optional[_np.ndarray] = None) -> bool:
        """Install a prefix (idempotent: an existing key only refreshes
        recency — concurrent admissions of the same prefix must not
        churn the rows).  Evicts LRU unreferenced entries past the
        ``slots`` bound; returns False when the cache is disabled or
        every resident entry is pinned."""
        if self.slots == 0:
            return False
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return True
            while len(self._entries) >= self.slots:
                victim = next((k for k, e in self._entries.items()
                               if e.refs == 0), None)
                if victim is None:
                    return False        # everything pinned: skip insert
                del self._entries[victim]
                _metrics.GEN_PREFIX_EVICTIONS_TOTAL.inc()
            self._entries[key] = _PrefixEntry(key, list(ks), list(vs),
                                              q, logits)
            _metrics.GEN_PREFIX_ROWS.set(
                sum(e.bucket for e in self._entries.values()))
            return True

    def attach_logits(self, key: str, logits: _np.ndarray) -> None:
        """Upgrade a resident entry with whole-prompt prefill logits
        (an entry first inserted from a LONGER prompt carries none;
        once some request's full prompt IS the prefix, its logits make
        every identical prompt admit with zero model calls)."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e.logits is None:
                e.logits = logits

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
        _metrics.GEN_PREFIX_ROWS.set(0)

    def rows_resident(self) -> int:
        with self._lock:
            return sum(e.bucket for e in self._entries.values())

    def describe(self) -> dict:
        with self._lock:
            return {
                "slots": self.slots,
                "entries": len(self._entries),
                "rows": sum(e.bucket for e in self._entries.values()),
                "pinned": sum(1 for e in self._entries.values()
                              if e.refs > 0),
            }

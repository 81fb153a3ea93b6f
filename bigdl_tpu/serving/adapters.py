"""Multi-tenant LoRA adapter serving: one quantized base, hundreds of
hot-swappable adapters (ISSUE 15; the ROADMAP "Multi-tenant LoRA
serving" item — the S-LoRA scenario, precedent in the reference's
FastChat multi-worker layer, SURVEY §L7).

The base model stays quantized and shared; each request may name a LoRA
adapter and the engine applies it as an UNQUANTIZED epilogue
``y += (x @ A) @ B * (alpha/r)`` on the shared fused dequant-GEMM
output (ops/linear.lora_epilogue) — never merge-and-requantize per
tenant (arxiv 2301.12017: requantizing a merged base compounds
quantization noise per adapter and would need a full base copy per
tenant's HBM).

Three pieces live here:

* **artifact I/O** — :func:`save_adapter` / :func:`load_adapter`: a
  LoRA tree as ONE .npz with a per-tensor integrity manifest
  (utils/durability.py), committed through the atomic
  tmp+fsync+rename protocol; loads verify in ``off|fast|full`` modes
  and raise a structured :class:`AdapterError` instead of a KeyError
  deep in a decode step;
* **AdapterRegistry** — named adapters resident in host RAM under a
  byte budget, O(1) LRU on hit, refcounted (a slot decoding with an
  adapter holds one reference — the same one-hold-per-holder rule as
  ``kvpaged.PagePool``; eviction only ever touches refcount-0,
  unpinned entries), lazy reload-by-name after eviction, and a
  seedable fault point (``adapter_load_corrupt`` in
  serving/faults.POINTS) so the corrupt-artifact path is an ordinary
  CPU test;
* **rank bucketing** — :func:`rank_bucket` rounds the max rank in a
  batch up a small power-of-two ladder, bounding the number of
  compiled decode/prefill variants: zero-padding A's rank rows and
  B's rank columns contributes exactly 0 to the epilogue, so one
  program serves every adapter at or below the bucket.

docs/serving.md §7 documents the full model.
"""

from __future__ import annotations

import collections
import json
import os
import time
import zipfile
from typing import Callable, Optional

import numpy as np

from bigdl_tpu.serving.faults import NULL_INJECTOR
from bigdl_tpu.utils import durability
from bigdl_tpu.utils.durability import IntegrityError

FORMAT_VERSION = 1

#: registry default: adapters above this rank are refused at load (the
#: bucketed decode program's cost grows with the bucket, and a single
#: huge-rank tenant would inflate every batch it rides in)
DEFAULT_MAX_RANK = 64


def rank_bucket(rank: int) -> int:
    """The compile-variant ladder: smallest power of two >= rank, with
    a floor of 4 (ranks 1-4 share one program)."""
    b = 4
    while b < rank:
        b *= 2
    return b


def lora_nbytes(lora: dict) -> int:
    """Host-RAM footprint of a LoRA tree's weight leaves — THE size the
    registry budgets, evicts on, and reports; `bigdl-tpu adapters
    inspect` and the sim's budget sizing use the same definition so an
    operator-observed nbytes always matches the accounting."""
    return sum(
        int(np.asarray(pair[leaf]).nbytes)
        for pair in lora["layers"].values() for leaf in ("a", "b")
    )


class AdapterError(ValueError):
    """Structured adapter failure. `kind` is machine-readable:

    - ``missing``: no artifact for the name (not resident, no path)
    - ``corrupt``: integrity verification failed (or injected via the
      ``adapter_load_corrupt`` fault point)
    - ``rank_mismatch``: rank/shape disagrees with the serving model
      (wrong base, a/b pair mismatch, or rank over the registry cap)
    - ``busy``: unload refused while requests hold references
    - ``budget``: the host-RAM budget cannot fit the adapter even
      after evicting every evictable entry
    - ``page_in_stall``: the device page-in of the adapter's weights
      stalled (injected via the ``adapter_page_in_stall`` fault point)
      — the request naming it finishes "error", never fail_all

    Subclasses ValueError so generic input-validation guards keep
    working; the HTTP layer maps kinds to status codes."""

    def __init__(self, name: str, kind: str, detail: str = ""):
        self.name = name
        self.kind = kind
        self.detail = detail
        super().__init__(
            f"adapter {name!r}: {kind}" + (f" — {detail}" if detail else "")
        )


# ---------------------------------------------------------------------------
# artifact I/O (durability manifests, atomic commit)
# ---------------------------------------------------------------------------

def save_adapter(path: str, lora: dict, *, faults=None) -> None:
    """Write a LoRA tree ({'layers': {target: {'a', 'b'}}, 'scale'}) as
    one verifiable .npz: per-tensor crc32/sha256 digests in the meta
    member, atomic tmp+fsync+rename commit. The serving handoff from
    train/qlora.py — a trained adapter becomes a durable artifact the
    registry can load, verify, and evict (docs/training.md)."""
    from bigdl_tpu.train.checkpoint import _encode

    arrays: dict = {}
    dtypes: dict = {}
    rank = None
    for t in sorted(lora["layers"]):
        pair = lora["layers"][t]
        a, b = np.asarray(pair["a"]), np.asarray(pair["b"])
        if a.ndim != 3 or b.ndim != 3 or a.shape[1] != b.shape[2]:
            raise AdapterError(
                os.path.basename(path), "rank_mismatch",
                f"target {t}: a {a.shape} / b {b.shape} are not "
                "[L, r, in] / [L, out, r] with one shared rank",
            )
        if rank is None:
            rank = a.shape[1]
        elif a.shape[1] != rank:
            raise AdapterError(
                os.path.basename(path), "rank_mismatch",
                f"target {t} rank {a.shape[1]} != {rank} (one rank per "
                "adapter)",
            )
        for leaf, arr in (("a", pair["a"]), ("b", pair["b"])):
            enc, dt = _encode(arr)
            arrays[f"layers/{t}/{leaf}"] = enc
            dtypes[f"layers/{t}/{leaf}"] = dt
    scale = float(np.asarray(lora["scale"], np.float32))

    def write(f) -> None:
        with zipfile.ZipFile(f, "w", zipfile.ZIP_STORED) as zf:
            tensors = {}
            for k in sorted(arrays):
                tensors[k] = durability.add_npz_member(zf, k, arrays[k])
            meta = {
                "format_version": FORMAT_VERSION,
                "rank": int(rank or 0),
                "scale": scale,
                "targets": sorted(lora["layers"]),
                "dtypes": dtypes,
                "integrity": durability.integrity_section(tensors),
            }
            durability.add_npz_member(zf, "meta",
                                      np.asarray(json.dumps(meta)))

    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    durability.atomic_write(path, write, faults=faults)


def load_adapter(path: str, verify: str = "fast") -> tuple[dict, dict]:
    """Read + verify one adapter artifact -> (lora tree with host
    numpy/bit-view leaves decoded to their logical dtypes, meta dict).
    verify: off|fast|full (utils/durability.py semantics). Raises
    FileNotFoundError for an absent file and IntegrityError for a
    damaged one — the registry wraps both into AdapterError."""
    from bigdl_tpu.train.checkpoint import _decode

    durability.check_verify_mode(verify)
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    try:
        npz = np.load(path, allow_pickle=False)
        meta = json.loads(str(npz["meta"]))
    except Exception as e:
        durability.VERIFY_FAILURES.inc()
        raise IntegrityError(
            path, detail=f"unreadable adapter: {type(e).__name__}: {e}",
        ) from e
    if meta.get("format_version") != FORMAT_VERSION:
        durability.VERIFY_FAILURES.inc()
        raise IntegrityError(
            path, detail=f"unsupported adapter format_version "
                         f"{meta.get('format_version')!r} (rotted meta?)",
        )
    targets = meta.get("targets") or []
    dtypes = meta.get("dtypes") or {}
    expected = [f"layers/{t}/{leaf}" for t in targets for leaf in ("a", "b")]
    integrity = (meta.get("integrity") or {}).get("tensors")
    arrays, corrupted, missing, extra = durability.verify_npz_members(
        path, integrity, verify, expected, ignore={"meta"},
    )
    if verify == "full":
        for k in expected:
            if k not in arrays:
                continue
            detail = durability.scan_non_finite(arrays[k], dtypes.get(k, ""))
            if detail is not None:
                corrupted[k] = f"non_finite: {detail}"
                arrays.pop(k)
    if corrupted or missing or extra:
        durability.VERIFY_FAILURES.inc()
        raise IntegrityError(path, corrupted=corrupted, missing=missing,
                             extra=extra)
    layers = {
        t: {leaf: _decode(arrays[f"layers/{t}/{leaf}"],
                          dtypes.get(f"layers/{t}/{leaf}", "float32"))
            for leaf in ("a", "b")}
        for t in targets
    }
    return {"layers": layers, "scale": float(meta.get("scale", 1.0))}, meta


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

class AdapterEntry:
    """One resident adapter: host-RAM weights + the cached rank-padded
    device trees the engine's prefill path feeds to the model. The
    registry owns `refcount`; holders (a slot decoding with this
    adapter, a parked preempted request) each carry exactly one."""

    __slots__ = ("name", "path", "layers", "scale", "rank", "alpha",
                 "targets", "nbytes", "pinned", "refcount", "_trees")

    def __init__(self, name: str, path: Optional[str], lora: dict,
                 meta: dict, pinned: bool = False):
        self.name = name
        self.path = path
        self.layers = lora["layers"]
        self.scale = float(lora["scale"])
        self.rank = int(meta.get("rank", 0))
        self.alpha = self.scale * max(self.rank, 1)
        self.targets = tuple(sorted(self.layers))
        self.nbytes = lora_nbytes(lora)
        self.pinned = pinned
        self.refcount = 0
        self._trees: dict = {}  # rank bucket -> device tree

    def tree(self, bucket: Optional[int] = None) -> dict:
        """The single-request LoRA tree at `bucket` rank (default: this
        adapter's own bucket), A zero-padded on rank rows and B on rank
        columns — exact zeros contribute nothing to the epilogue, so
        every adapter at or below the bucket shares one compiled
        prefill/decode variant."""
        import jax.numpy as jnp

        rb = rank_bucket(self.rank) if bucket is None else bucket
        if rb in self._trees:
            return self._trees[rb]
        layers = {}
        for t, pair in self.layers.items():
            a = jnp.asarray(pair["a"])
            b = jnp.asarray(pair["b"])
            if rb > self.rank:
                a = jnp.pad(a, ((0, 0), (0, rb - self.rank), (0, 0)))
                b = jnp.pad(b, ((0, 0), (0, 0), (0, rb - self.rank)))
            layers[t] = {"a": a, "b": b}
        tree = {"layers": layers,
                "scale": jnp.asarray(self.scale, jnp.float32)}
        self._trees[rb] = tree
        return tree

    def describe(self) -> dict:
        return {
            "name": self.name, "rank": self.rank, "alpha": self.alpha,
            "targets": list(self.targets), "nbytes": self.nbytes,
            "pinned": self.pinned, "refcount": self.refcount,
        }


class AdapterRegistry:
    """Named LoRA adapters resident in host RAM under `budget_bytes`.

    Thread-safe: HTTP handler threads load/unload/pin while the engine
    thread acquires/releases per request. LRU is an OrderedDict
    (`move_to_end` on every hit, O(1) — serving/radix.py's discipline);
    eviction scans LRU-first for an entry no request references and no
    operator pinned. An evicted name is NOT forgotten: its path stays
    registered, so the next request naming it triggers a (counted)
    reload — the churn the sim's Zipf trace prices.

    `verify` (default "fast") is the load-time integrity mode; the
    ``adapter_load_corrupt`` fault point (serving/faults.py) makes the
    corrupt path deterministic in tests."""

    def __init__(self, dir: Optional[str] = None,
                 budget_bytes: Optional[int] = None,
                 verify: str = "fast",
                 max_rank: int = DEFAULT_MAX_RANK,
                 faults=None, tracer=None,
                 clock: Callable[[], float] = time.time):
        import threading

        self.dir = dir
        self.budget_bytes = budget_bytes
        self.verify = durability.check_verify_mode(verify)
        self.max_rank = max_rank
        self._faults = faults if faults is not None else NULL_INJECTOR
        self.tracer = tracer
        self._clock = clock
        self._lock = threading.RLock()
        # name -> entry, least-recently-used first
        self._entries: "collections.OrderedDict[str, AdapterEntry]" = \
            collections.OrderedDict()
        self._paths: dict[str, str] = {}  # every name ever loaded
        # observability (serving/metrics.py renders these)
        self.loads = 0          # artifact reads (incl. post-evict reloads)
        self.hits = 0           # get() served from residency
        self.evictions = 0      # budget-pressure drops
        self.load_failures = 0  # missing/corrupt/mismatched artifacts

    def bind(self, tracer=None, clock=None,
             faults=None) -> "AdapterRegistry":
        """Late wiring for servers that construct their tracer/clock/
        injector after the registry (ApiServer does). An injector the
        registry was EXPLICITLY constructed with is never clobbered —
        the server's only fills the inert default, so arming
        adapter_load_corrupt on the server-level injector reaches the
        registry too."""
        if tracer is not None:
            self.tracer = tracer
        if clock is not None:
            self._clock = clock
        if faults is not None and self._faults is NULL_INJECTOR:
            self._faults = faults
        return self

    # -- internals (call with the lock held) --------------------------------

    def _instant(self, event: str, **args) -> None:
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant(event, ts=self._clock(), tid=0, cat="adapter",
                       **args)

    def _resolve_path(self, name: str, path: Optional[str]) -> str:
        if path is not None:
            return path
        if name in self._paths:
            return self._paths[name]
        if self.dir is not None:
            cand = os.path.join(self.dir, f"{name}.npz")
            if os.path.exists(cand):
                return cand
            cand = os.path.join(self.dir, name)
            if os.path.exists(cand):
                return cand
        raise AdapterError(
            name, "missing",
            "not resident and no artifact path known"
            + (f" under {self.dir}" if self.dir else
               " (no adapter dir configured)"),
        )

    def _resident_bytes(self) -> int:
        return sum(e.nbytes for e in self._entries.values())

    def _evict_for(self, name: str, nbytes: int) -> None:
        """Free budget room for `nbytes`, LRU-first, refcount-0 and
        unpinned entries only — an adapter a slot is decoding with (or
        a parked request will resume with) is never dropped."""
        if self.budget_bytes is None:
            return
        while self._resident_bytes() + nbytes > self.budget_bytes:
            victim = None
            for e in self._entries.values():  # LRU -> MRU
                if e.refcount == 0 and not e.pinned:
                    victim = e
                    break
            if victim is None:
                raise AdapterError(
                    name, "budget",
                    f"{nbytes} bytes over budget "
                    f"{self.budget_bytes} and every resident adapter "
                    "is referenced or pinned",
                )
            del self._entries[victim.name]
            self.evictions += 1
            self._instant("adapter_evict", name=victim.name,
                          nbytes=victim.nbytes)

    def _load_locked(self, name: str, path: Optional[str],
                     pin: bool) -> AdapterEntry:
        resolved = self._resolve_path(name, path)
        t0 = self._clock()
        if self._faults.fire("adapter_load_corrupt") is not None:
            self.load_failures += 1
            raise AdapterError(
                name, "corrupt",
                f"injected corrupt artifact ({resolved}; fault point "
                "adapter_load_corrupt)",
            )
        try:
            lora, meta = load_adapter(resolved, verify=self.verify)
        except FileNotFoundError as e:
            self.load_failures += 1
            raise AdapterError(name, "missing", str(e)) from e
        except IntegrityError as e:
            self.load_failures += 1
            raise AdapterError(name, "corrupt", str(e)) from e
        entry = AdapterEntry(name, resolved, lora, meta, pinned=pin)
        if entry.rank < 1 or entry.rank > self.max_rank:
            self.load_failures += 1
            raise AdapterError(
                name, "rank_mismatch",
                f"rank {entry.rank} outside [1, {self.max_rank}] "
                "(registry max_rank)",
            )
        self._evict_for(name, entry.nbytes)
        self._entries[name] = entry  # most-recently-used
        self._paths[name] = resolved
        self.loads += 1
        self._instant("adapter_load", name=name, rank=entry.rank,
                      nbytes=entry.nbytes,
                      seconds=round(self._clock() - t0, 6))
        return entry

    # -- operator surface ----------------------------------------------------

    def load(self, name: str, path: Optional[str] = None,
             pin: bool = False) -> dict:
        """Load (or reload) an adapter into residency; returns its
        description. POST /adapters/load lands here."""
        with self._lock:
            old = self._entries.get(name)
            if old is not None and old.refcount > 0:
                # a reload under live references would swap weights
                # mid-decode for those requests; keep it explicit
                raise AdapterError(
                    name, "busy",
                    f"{old.refcount} in-flight request(s) hold it; "
                    "unload requires refcount 0",
                )
            if old is not None:
                # drop the old entry for the duration of the load so
                # _evict_for doesn't double-count its bytes — but a
                # FAILED reload (typo'd path, corrupt artifact) must
                # not cost the healthy resident entry or its pin
                del self._entries[name]
            try:
                entry = self._load_locked(name, path, pin)
            except Exception:
                if old is not None:
                    self._entries[name] = old  # restore, MRU position
                raise
            return entry.describe()

    def unload(self, name: str) -> dict:
        """Drop an adapter from residency (its path stays known, so a
        later request can lazily reload it). Refused while referenced."""
        with self._lock:
            entry = self._entries.get(name)
            if entry is None:
                raise AdapterError(name, "missing", "not resident")
            if entry.refcount > 0:
                raise AdapterError(
                    name, "busy",
                    f"{entry.refcount} in-flight request(s) hold it",
                )
            del self._entries[name]
            self._instant("adapter_unload", name=name)
            return entry.describe()

    def pin(self, name: str, pinned: bool = True) -> dict:
        with self._lock:
            entry = self._entries.get(name)
            if entry is None:
                raise AdapterError(name, "missing", "not resident")
            entry.pinned = pinned
            return entry.describe()

    # -- engine surface ------------------------------------------------------

    def get(self, name: str) -> AdapterEntry:
        """The entry for `name`, LRU-refreshed; lazily reloads an
        evicted (or never-loaded, when `dir` is set) adapter."""
        with self._lock:
            entry = self._entries.get(name)
            if entry is not None:
                self._entries.move_to_end(name)
                self.hits += 1
                return entry
            return self._load_locked(name, None, pin=False)

    def acquire(self, name: str) -> AdapterEntry:
        """get() + one reference: the caller (an admitted request) now
        holds the adapter resident until release()."""
        with self._lock:
            entry = self.get(name)
            entry.refcount += 1
            return entry

    def release(self, entry: AdapterEntry) -> None:
        with self._lock:
            entry.refcount -= 1
            if entry.refcount < 0:  # double-release corrupts the budget
                # accounting silently later; fail at the faulting site
                # (kvpaged.PagePool.decref's discipline)
                raise AssertionError(
                    f"adapter {entry.name!r} refcount went negative"
                )

    def reject(self, entry: AdapterEntry, held: bool = True) -> None:
        """Release (when the caller holds a reference) + drop an entry
        the CALLER found unusable — dimension validation happens
        against the serving model, which the registry cannot see.
        Counted as a load failure (the artifact is as broken for this
        deployment as a corrupt one) and evicted from residency so it
        neither squats on budget nor serves `hits` to every retry of
        the doomed tenant."""
        with self._lock:
            if held:
                self.release(entry)
            self.load_failures += 1
            if (self._entries.get(entry.name) is entry
                    and entry.refcount == 0):
                del self._entries[entry.name]
                self._instant("adapter_evict", name=entry.name,
                              nbytes=entry.nbytes, rejected=True)

    def peek(self, name: str) -> Optional[AdapterEntry]:
        """The resident entry for `name`, with NO side effects — no LRU
        refresh, no hit count, no lazy reload (validation paths must
        not skew the churn counters request traffic is measured by)."""
        with self._lock:
            return self._entries.get(name)

    # -- observability -------------------------------------------------------

    def resident(self) -> list:
        with self._lock:
            return [e.describe() for e in self._entries.values()]

    def stats(self) -> dict:
        with self._lock:
            return {
                "loads": self.loads,
                "hits": self.hits,
                "evictions": self.evictions,
                "load_failures": self.load_failures,
                "resident": len(self._entries),
                "resident_bytes": self._resident_bytes(),
                "budget_bytes": self.budget_bytes,
            }


# ---------------------------------------------------------------------------
# unified HBM paging: adapter weights in the KV page pool
# ---------------------------------------------------------------------------

class _PagedAdapter:
    """One device-resident adapter: its physical pages (each carrying
    the pager's ONE PagePool reference), the leaf shapes needed to
    reconstruct (A, B) from the flat page frame, and the rids holding
    it resident (one hold per in-flight request — the same
    one-hold-per-holder rule as the registry and the PagePool)."""

    __slots__ = ("name", "pages", "shapes", "n_elems", "holders")

    def __init__(self, name, pages, shapes, n_elems):
        self.name = name
        self.pages = pages
        self.shapes = shapes
        self.n_elems = n_elems
        self.holders: set = set()


class AdapterPager:
    """Device residency for resident adapters' (A, B) weight leaves,
    allocated from the serving engine's KV :class:`kvpaged.PagePool` —
    ONE HBM budget for KV and adapters (the S-LoRA unified paging
    model, docs/serving.md §7). Engine-thread only (no lock): page-in
    happens at admission, page-out under the engine's own allocation
    escalation.

    Lifecycle:

    * **page-in** (:meth:`ensure`): flatten the entry's host leaves at
      its OWN rank (bucket padding happens at gather time, device
      side), allocate pages through the engine's radix-escalated
      allocator, scatter into the :class:`kvpaged.AdapterPageStore`.
      A dry pool (even after radix eviction) is NOT fatal: the caller
      falls back to host-sourced gathers for that adapter — page-in
      never preempts KV.
    * **page-out** (:meth:`evict_one`): LRU-first holder-free adapter
      drops its device pages (decref -> free list). The host copy in
      the AdapterRegistry survives, so "page-out to host" is a free
      drop, and the next request naming the tenant pages back in.
    * eviction order under page pressure (PageTable.alloc): radix
      leaf -> refcount-0 adapter page-out -> preemption.

    ``scale`` stays host-side registry metadata (f32) — only the bf16
    A/B leaves are paged, so paging is parity-exact with the host path
    (the epilogue computes in bf16 either way)."""

    def __init__(self, store, pool, alloc: Callable[[], Optional[int]],
                 faults=None):
        self.store = store
        self._pool = pool
        self._alloc = alloc
        self._faults = faults if faults is not None else NULL_INJECTOR
        # name -> _PagedAdapter, least-recently-used first
        self._res: "collections.OrderedDict[str, _PagedAdapter]" = \
            collections.OrderedDict()
        # observability (serving/metrics.py + the sim report)
        self.page_ins = 0   # pages written device-ward
        self.page_outs = 0  # pages dropped back to the free list

    @property
    def pages_resident(self) -> int:
        return sum(len(r.pages) for r in self._res.values())

    def held_pages(self):
        for rec in self._res.values():
            yield from rec.pages

    def ensure(self, entry: AdapterEntry, rid: int) -> bool:
        """Make `entry` device-resident and add `rid`'s hold. False =
        the pool stayed dry after eviction (caller uses host fallback).
        Raises AdapterError(kind="page_in_stall") when the fault point
        fires — the caller quarantines ONE request, never the batch."""
        rec = self._res.get(entry.name)
        if rec is not None:
            self._res.move_to_end(entry.name)
            rec.holders.add(rid)
            return True
        if self._faults.fire("adapter_page_in_stall") is not None:
            raise AdapterError(
                entry.name, "page_in_stall",
                "injected device page-in stall (fault point "
                "adapter_page_in_stall)",
            )
        flats, shapes = [], []
        for t in entry.targets:
            for leaf in ("a", "b"):
                arr = np.asarray(entry.layers[t][leaf], np.float32)
                shapes.append((t, leaf, arr.shape))
                flats.append(arr.ravel())
        flat = (np.concatenate(flats) if flats
                else np.zeros((0,), np.float32))
        pages: list = []
        for _ in range(self.store.n_for(flat.size)):
            pg = self._alloc()
            if pg is None:
                # dry even after radix + adapter eviction: give the
                # pages back and serve this tenant from host RAM —
                # admission semantics are unchanged, only the gather
                # source differs
                for p in pages:
                    self._pool.decref(p)
                return False
            pages.append(pg)
        try:
            self.store.write(pages, flat)
        except Exception:
            # the device scatter is a fault point (host OOM, bad
            # artifact dtype, injected stall): its raise must not
            # strand the freshly-allocated page refs — nothing holds
            # them yet, so give them straight back and let the caller
            # quarantine the one request (graftlint PAGE002)
            for p in pages:
                self._pool.decref(p)
            raise
        self.page_ins += len(pages)
        rec = _PagedAdapter(entry.name, pages, shapes, int(flat.size))
        rec.holders.add(rid)
        self._res[entry.name] = rec  # most-recently-used
        return True

    def leaves(self, name: str) -> Optional[dict]:
        """Device-side {target: {'a', 'b'}} bf16 leaves for a RESIDENT
        adapter (LRU-refreshed), or None — the engine's _gather_blora
        reads pages instead of re-transferring host weights."""
        rec = self._res.get(name)
        if rec is None:
            return None
        self._res.move_to_end(name)
        flat = self.store.read(rec.pages, rec.n_elems)
        out: dict = {}
        off = 0
        for t, leaf, shape in rec.shapes:
            sz = 1
            for d in shape:
                sz *= int(d)
            out.setdefault(t, {})[leaf] = flat[off:off + sz].reshape(shape)
            off += sz
        return out

    def drop_holder(self, rid: int) -> None:
        """Release `rid`'s holds (terminal finish). The adapter STAYS
        resident — holder-free residency is what the LRU evicts under
        pressure, not what release drops (warm reuse is the point)."""
        for rec in self._res.values():
            rec.holders.discard(rid)

    def evict_one(self) -> bool:
        """Page out the LRU holder-free adapter; False when every
        resident adapter is held (the allocator escalates to
        preemption)."""
        victim = None
        for rec in self._res.values():  # LRU -> MRU
            if not rec.holders:
                victim = rec
                break
        if victim is None:
            return False
        for pg in victim.pages:
            self._pool.decref(pg)
        self.page_outs += len(victim.pages)
        del self._res[victim.name]
        return True

    def reset(self, pool, alloc: Callable[[], Optional[int]]) -> None:
        """Post-crash rebuild (PageTable.rebuilt): the old PagePool
        died with the cache, so residency is simply forgotten — no
        decrefs against a pool that no longer exists. Counters survive
        (engine totals, not cache state)."""
        self._pool = pool
        self._alloc = alloc
        self._res.clear()

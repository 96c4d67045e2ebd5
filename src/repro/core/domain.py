"""Unified memory-domain API: one pytree-native HRM object.

The paper's core abstraction is a *memory domain*: a set of memory regions
bound to reliability tiers, scrubbed and recovered as a unit.
``MemoryDomain`` is one ``jax.tree_util``-registered container owning

    payload          the protected state pytree — multiple roots at once
                     (``params``, ``opt/m``, ``opt/v``, ``kv_cache``)
    sidecar          per-*tier* ECC/parity buffers
    hard_error_map   live sticky (hard) errors, re-asserted on writes
    policy + plan    static region->tier assignment and buffer layout

and a verb API: ``MemoryDomain.protect(state, policy)``, ``.scrub(step)``,
``.scrub_partial(cursor)``, ``.recover(report, ...)``, ``.inject(rng, n,
hard=)``, ``.refresh(state, paths=)``, ``.refresh_pages(pages, state)``,
``.stats()``.

Execution model — row sets. Every protected leaf packs into ``(rows,
LANES)`` 64-bit words; a tier's leaves lie end to end in its sidecar
buffers, one sidecar row per packed row. Every pass is a ``RowSet``: per
tier, the packed rows it reads and the sidecar rows it writes — whole
leaves for ``scrub``/``refresh`` (or a ``paths`` subset), a cursor's
window for ``scrub_partial``, whole leaves at a traced page index for
``refresh_pages``. One scrub and one encode program are built from a row
set, each one jit computation cached per (layout, row set): per tier, the
rows are gathered into one buffer, the tier's kernel (``TIER_CODES``)
runs over it, and the results go back where they came from; a tier's
whole buffer passes through with no gather or scatter. Per-word ECC math
is position-independent, so results are bit-identical to the legacy
per-leaf path (``tests/test_domain.py``). Programs lower as
``jit_<kind>_scrub``, ``_scrub_slice`` (a window), ``_encode`` (every
whole buffer, or pages) and ``_encode_rows`` (a static subset), ``kind``
the payload's root kind.

Pad rows (to make row counts divide the kernel block) hold zero words whose
code bits are also zero (every tier's code is linear), so padding
contributes no corrections.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import (Any, Callable, Dict, Iterable, List, NamedTuple,
                    Optional, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import RegionProfile
from repro.core.errormodel import InjectionPlan
from repro.core.policy import HRMPolicy, classify_path
from repro.core.recovery import (Response, RestartRequired, RetirementMap,
                                 flagged_blocks)
from repro.core.sidecar import ScrubReport, _path_str
from repro.core.tiers import Tier
from repro.kernels import interpret_mode, ops
from repro.kernels.burst import burst_encode_words, burst_scrub_words
from repro.kernels.dected import dected_encode_words, dected_scrub_words
from repro.kernels.ops import BLOCK_ROWS, LANES, _round_rows
from repro.kernels.parity import parity_check_words, parity_encode_words
from repro.kernels.secded import secded_encode_words, secded_scrub_words

# top-level payload keys recognized as roots with their classifier kind
_ROOT_KIND = {"params": "params", "opt": "opt", "kv_cache": "cache",
              "cache": "cache", "graph": "graph"}


class LeafSpec(NamedTuple):
    """Static description of one payload leaf (hashable: jit cache key)."""
    path: str                  # full path string, root prefix included
    pos: int                   # index into the flattened payload leaves
    region: str                # HRM region (policy granularity)
    tier: Tier
    shape: Tuple[int, ...]
    dtype: str
    rows: int                  # packed (rows, LANES) 64-bit-word rows
    row_start: int             # row offset in its tier buffer (-1: NONE)

    @property
    def nbytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n * jnp.dtype(self.dtype).itemsize


Piece = Tuple[LeafSpec, int, int]      # a leaf's packed rows a .. b


class RowSet(NamedTuple):
    """The rows one pass reads and writes: per tier, in tier order, the
    leaf-local packed row ranges ``(leaf, a, b)``; they sit at rows
    ``row_start + a .. row_start + b`` of the tier's sidecar buffers.
    ``window``: a cursor's slice of a selection, which may cut leaves.
    ``pages``: whole leaves read only at a traced index along their
    second axis. Hashable: it keys the program caches."""
    tiers: Tuple[Tuple[Tier, Tuple[Piece, ...]], ...]
    window: bool = False
    pages: bool = False


def _key_str(entry) -> str:
    return str(getattr(entry, "key", getattr(entry, "name", entry)))


def _classify(path) -> str:
    """Region of a full-payload path: the first key selects the root kind
    (``params``/``opt``/``kv_cache``); bare params trees classify whole."""
    if len(path) > 1:
        kind = _ROOT_KIND.get(_key_str(path[0]).lower())
        if kind is not None:
            return classify_path(path[1:], kind)
    return classify_path(path, "params")


def _root_kind(path: str) -> str:
    """Root kind of a leaf path string, as ``_classify`` reads it."""
    root, sep, _ = path.partition("/")
    return _ROOT_KIND.get(root.lower(), "params") if sep else "params"


def _jit_named(fn: Callable, name: str, **jit_kw) -> Callable:
    """``jax.jit(fn, **jit_kw)`` lowered as module ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kw)


def _supported(leaf) -> bool:
    if not hasattr(leaf, "dtype") or not hasattr(leaf, "shape"):
        return False
    return jnp.dtype(leaf.dtype).itemsize in (1, 2, 4)


class DomainSpec:
    """Static layout of a domain: policy + leaf table + tier grouping.

    Hashable/eq-comparable so it can ride in pytree ``aux_data`` (treedefs
    compare by it) and key the jit caches for scrub/encode programs.
    """
    __slots__ = ("policy", "leaves", "treedef", "groups", "by_path",
                 "protectable", "kind", "_byte_weights", "_hash")

    def __init__(self, policy: HRMPolicy, leaves: Tuple[LeafSpec, ...],
                 treedef):
        self.policy = policy
        self.leaves = leaves
        self.treedef = treedef
        grouped: Dict[Tier, List[LeafSpec]] = {}
        for s in leaves:
            if s.tier is not Tier.NONE:
                grouped.setdefault(s.tier, []).append(s)
        self.groups: Dict[Tier, Tuple[LeafSpec, ...]] = {
            t: tuple(ls) for t, ls in grouped.items()}
        self.by_path = {s.path: s for s in leaves}
        self.protectable = tuple(s for s in leaves if s.rows > 0)
        # root kind of the payload (``domain`` for a mixed one): it names
        # the compiled programs, so a device trace tells them apart
        kinds = {_root_kind(s.path) for s in leaves}
        self.kind = kinds.pop() if len(kinds) == 1 else "domain"
        w = np.array([s.nbytes for s in self.protectable], dtype=np.float64)
        self._byte_weights = w / w.sum() if w.size and w.sum() > 0 else w
        self._hash = hash((policy, leaves, treedef))

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return (isinstance(other, DomainSpec)
                and self.policy == other.policy
                and self.leaves == other.leaves
                and self.treedef == other.treedef)

    # ------------------------------------------------- subset selection
    def paths_key(self, paths: Optional[Iterable[str]]
                  ) -> Optional[Tuple[str, ...]]:
        """Normalize a path subset into a hashable key (in leaf order) for
        ``row_set``; None selects every protected leaf."""
        if paths is None:
            return None
        want = set(paths)
        return tuple(s.path for s in self.leaves
                     if s.path in want and s.tier is not Tier.NONE)

    def slices_aligned(self) -> bool:
        """Whether every protected leaf's slices along its second axis are
        whole packed rows, so ``refresh_pages`` can encode them alone."""
        return all(_slice_rows(s) for ls in self.groups.values()
                   for s in ls)

    def row_set(self, key: Optional[Tuple[str, ...]], idx: int = 0,
                slices: int = 1, pages: bool = False) -> RowSet:
        """The rows of the ``key`` selection (``paths_key``): its leaves
        whole or, with ``slices`` > 1, window ``idx``; with ``pages``, read
        at a traced page index (``slices_aligned``). A window is cut per
        tier from the selected leaves' rows laid end to end, so every tier
        advances each call and finishes together after ``slices`` calls
        (``MemoryDomain.scrub_partial``)."""
        want = None if key is None else set(key)
        tiers = []
        for tier in sorted(self.groups, key=lambda t: t.value):
            sel = [s for s in self.groups[tier]
                   if want is None or s.path in want]
            total = sum(s.rows for s in sel)
            lo, hi = idx * total // slices, (idx + 1) * total // slices
            pieces, off = [], 0
            for s in sel:
                a, b = max(lo - off, 0), min(hi - off, s.rows)
                if a < b:
                    pieces.append((s, a, b))
                off += s.rows
            if pieces:
                tiers.append((tier, tuple(pieces)))
        return RowSet(tuple(tiers), slices > 1, pages)


# =====================================================================
# row-set programs: one scrub and one encode, tier-batched
# =====================================================================
class TierCode(NamedTuple):
    """A tier's kernels and stored sidecar, read by every pass."""
    buf: str            # ``ecc`` (the scrub corrects) or ``par`` (detects)
    dtype: Any          # the code words' stored dtype
    encode: Callable    # (lo, hi, block_rows=) -> code words
    scrub: Callable     # ECC: -> (lo, hi, ecc, corrected, uncorrectable);
    #                     parity: -> (error bits, count)
    copy: bool = False  # keeps the words too (MIRROR): parity errors are
    #                     repaired from them; parity alone is detect only


TIER_CODES: Dict[Tier, TierCode] = {
    Tier.SECDED: TierCode("ecc", jnp.uint8, secded_encode_words,
                          secded_scrub_words),
    Tier.DECTED: TierCode("ecc", jnp.uint16, dected_encode_words,
                          dected_scrub_words),
    Tier.BURST: TierCode("ecc", jnp.uint16, burst_encode_words,
                         burst_scrub_words),
    Tier.PARITY_R: TierCode("par", jnp.uint8, parity_encode_words,
                            parity_check_words),
    Tier.MIRROR: TierCode("par", jnp.uint8, parity_encode_words,
                          parity_check_words, copy=True),
}


def _concat_pad(arrs: List[jax.Array], padded: int) -> jax.Array:
    x = arrs[0] if len(arrs) == 1 else jnp.concatenate(arrs, axis=0)
    pad = padded - x.shape[0]
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


def _parity_mask(err: jax.Array, like: jax.Array) -> jax.Array:
    """Packed (rows, LANES//8) parity-error bits -> (rows, LANES) bool."""
    bits = (err[..., :, None] >> jnp.arange(8, dtype=jnp.uint32)) & 1
    return bits.reshape(like.shape).astype(jnp.bool_)


def _block_rows(padded: int) -> int:
    """Kernel block height for a batched tier buffer. On TPU the 128-row
    VMEM tile is the right block; in interpret mode (CPU) the emulator
    re-materializes every operand per grid step, so one grid step over the
    whole buffer is the fast path."""
    return padded if interpret_mode() else min(BLOCK_ROWS, padded)


def _slice_rows(s: LeafSpec) -> int:
    """Packed rows per index of the leaf's second axis, or 0 where such a
    slice is not a whole number of rows (or the leaf has no second axis).
    Row ``r`` holds the leaf's flat bytes ``8 LANES r ..``, so slice ``i``
    of leading index ``l`` is rows ``(l n1 + i) R .. + R``."""
    if len(s.shape) < 2:
        return 0
    nbytes = jnp.dtype(s.dtype).itemsize
    for d in s.shape[2:]:
        nbytes *= d
    rows, rem = divmod(nbytes, 8 * LANES)
    return rows if not rem else 0


class _Gathered(NamedTuple):
    """One tier's row set, gathered for its kernel."""
    lo: jax.Array               # (padded rows, LANES) packed words
    hi: jax.Array
    packed: List[ops.Packed]    # each static piece's whole leaf, packed
    at: Any                     # the rows' place in the sidecar buffers:
    # None (the whole buffer), static (start, stop) spans, or traced rows


def _gather(spec: DomainSpec, tier: Tier, leaves, pieces: Tuple[Piece, ...],
            idx=None) -> _Gathered:
    """Pack one tier's pieces into ``lo, hi`` padded by ``_round_rows``.
    With a traced page index ``idx`` only the slices ``leaf[:, idx]`` are
    packed, and their sidecar rows are computed from it."""
    if idx is None:
        packed = [ops.pack_words(leaves[s.pos]) for s, _, _ in pieces]
        lo = [p.lo[a:b] for p, (_, a, b) in zip(packed, pieces)]
        hi = [p.hi[a:b] for p, (_, a, b) in zip(packed, pieces)]
        n = sum(b - a for _, a, b in pieces)
        whole = pieces == tuple((s, 0, s.rows) for s in spec.groups[tier])
        at = None if whole else tuple((s.row_start + a, s.row_start + b)
                                      for s, a, b in pieces)
    else:
        packed, lo, hi, at = [], [], [], []
        for s, _, _ in pieces:
            r = _slice_rows(s)
            p = ops.pack_words(leaves[s.pos][:, idx])
            lo.append(p.lo[:s.shape[0] * idx.shape[0] * r])
            hi.append(p.hi[:s.shape[0] * idx.shape[0] * r])
            first = (jnp.arange(s.shape[0], dtype=jnp.int32)[:, None]
                     * s.shape[1] + idx[None, :]) * r + s.row_start
            at.append((first[:, :, None]
                       + jnp.arange(r, dtype=jnp.int32)).reshape(-1))
        at = jnp.concatenate(at)
        n = at.shape[0]
    padded = _round_rows(n)
    return _Gathered(_concat_pad(lo, padded), _concat_pad(hi, padded),
                     packed, at)


def _pull(g: _Gathered, buf: jax.Array) -> jax.Array:
    """The gathered rows of a sidecar buffer, padded as ``g.lo`` is."""
    if g.at is None:
        return buf
    return _concat_pad([buf[a:b] for a, b in g.at], g.lo.shape[0])


def _put(g: _Gathered, buf: Optional[jax.Array], new: jax.Array
         ) -> jax.Array:
    """``buf`` with the gathered rows set to ``new``'s; ``new`` itself
    where the rows are the whole buffer."""
    if g.at is None:
        return new
    if not isinstance(g.at, tuple):
        return buf.at[g.at].set(new[:g.at.shape[0]])
    o = 0
    for a, b in g.at:
        buf = buf.at[a:b].set(new[o:o + b - a])
        o += b - a
    return buf


@functools.lru_cache(maxsize=None)
def _compiled_scrub(spec: DomainSpec, rows: RowSet) -> Callable:
    """One jit program scrubbing a row set, tier-batched.

    fn(leaves_tuple, sidecar) -> (modified {pos: leaf}, new_sidecar,
    corrected {path: n}, detected_uncorrectable {path: n}). A leaf that
    a window cuts is spliced back at row granularity, bit-identical
    outside the window. PARITY_R's counts land in the uncorrectable
    column, and its data and sidecar are left as they were.
    """
    def fn(leaves, sidecar):
        mod: Dict[int, jax.Array] = {}
        new_sc = {k: dict(v) for k, v in sidecar.items()}
        corr: Dict[str, jax.Array] = {}
        unc: Dict[str, jax.Array] = {}
        for tier, pieces in rows.tiers:
            code, sc = TIER_CODES[tier], sidecar[tier.value]
            g = _gather(spec, tier, leaves, pieces)
            bm = _block_rows(g.lo.shape[0])
            stored = _pull(g, sc[code.buf]).astype(jnp.uint32)
            if code.buf == "ecc":
                lo, hi, ecc, c, u = code.scrub(g.lo, g.hi, stored,
                                               block_rows=bm)
                new_sc[tier.value]["ecc"] = _put(g, sc["ecc"],
                                                 ecc.astype(code.dtype))
            else:
                err, u = code.scrub(g.lo, g.hi, stored, block_rows=bm)
                if code.copy:
                    mask = _parity_mask(err, g.lo)
                    lo = jnp.where(mask, _pull(g, sc["copy_lo"]), g.lo)
                    hi = jnp.where(mask, _pull(g, sc["copy_hi"]), g.hi)
                    c = jnp.sum(mask.astype(jnp.int32), axis=1,
                                keepdims=True)
                    u = jnp.zeros_like(c)
            o = 0
            for (s, a, b), p in zip(pieces, g.packed):
                sl = slice(o, o + b - a)
                o += b - a
                unc[s.path] = jnp.sum(u[sl])
                if code.buf == "ecc" or code.copy:
                    x = ops.Packed(lo[sl], hi[sl])
                    if b - a < s.rows:
                        x = ops.Packed(p.lo.at[a:b].set(x.lo),
                                       p.hi.at[a:b].set(x.hi))
                    mod[s.pos] = ops.unpack_words(x, s.shape,
                                                  jnp.dtype(s.dtype))
                    corr[s.path] = jnp.sum(c[sl])
        return mod, new_sc, corr, unc

    return _jit_named(fn, f"{spec.kind}_scrub"
                      + ("_slice" if rows.window else ""))


@functools.lru_cache(maxsize=None)
def _compiled_encode(spec: DomainSpec, rows: RowSet) -> Callable:
    """One jit program encoding a row set's sidecar rows, tier-batched.

    Every tier's whole buffer (``spec.row_set(None)``): fn(leaves) ->
    fresh sidecar. A static subset: fn(leaves, sidecar) -> sidecar with
    only its rows rewritten. A page set: fn(leaves, sidecar, idx), the
    sidecar donated so the scatter updates it in place; a repeated page
    writes the same rows twice. Every tier's code is per 64-bit word, so
    the rows come out as a full encode's, bit for bit, wherever only
    they changed. The gather ``leaf[:, idx]`` is cheap where the second
    axis is not the most minor in device memory: see ``serve.paged_kv``
    for the pool layout that keeps it so on a TPU.
    """
    def fn(leaves, sidecar=None, idx=None):
        new_sc = {k: dict(v) for k, v in (sidecar or {}).items()}
        for tier, pieces in rows.tiers:
            code = TIER_CODES[tier]
            g = _gather(spec, tier, leaves, pieces, idx)
            fresh = {code.buf: code.encode(
                g.lo, g.hi, block_rows=_block_rows(g.lo.shape[0])
            ).astype(code.dtype)}
            if code.copy:
                fresh.update(copy_lo=g.lo, copy_hi=g.hi)
            out = new_sc.setdefault(tier.value, {})
            for name, new in fresh.items():
                out[name] = _put(g, out.get(name), new)
        return new_sc

    if rows.pages:
        return _jit_named(fn, f"{spec.kind}_encode", donate_argnums=(1,))
    whole = rows == spec.row_set(None)
    return _jit_named(fn, f"{spec.kind}_encode" + ("" if whole
                                                   else "_rows"))


# =====================================================================
# the domain object
# =====================================================================
@dataclass(frozen=True)
class DomainStats:
    """Measured footprint of a domain (no device sync needed)."""
    payload_bytes: int
    sidecar_bytes: int
    n_leaves: int
    n_protected: int
    n_hard_errors: int
    region_bytes: Dict[str, int]
    region_tiers: Dict[str, str]

    @property
    def overhead(self) -> float:
        return self.sidecar_bytes / max(self.payload_bytes, 1)

    def summary(self) -> str:
        return (f"payload={self.payload_bytes}B sidecar={self.sidecar_bytes}B"
                f" ({self.overhead:.2%}) leaves={self.n_protected}"
                f"/{self.n_leaves} protected, "
                f"hard_errors={self.n_hard_errors}")


@jax.tree_util.register_pytree_node_class
class MemoryDomain:
    """A reliability domain: payload + sidecar + policy + hard-error map.

    Functional style — every verb returns a new ``MemoryDomain`` sharing
    untouched buffers. Registered as a pytree: jit/vmap/scan see the
    payload, sidecar, and hard-error arrays as children and the static
    layout (``DomainSpec``) as aux data.
    """

    def __init__(self, payload, sidecar, hard_errors, spec: DomainSpec):
        self.payload = payload
        self.sidecar = sidecar
        self.hard_errors = hard_errors
        self.spec = spec

    # --------------------------------------------------------- pytree
    def tree_flatten(self):
        return (self.payload, self.sidecar, self.hard_errors), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        payload, sidecar, hard_errors = children
        return cls(payload, sidecar, hard_errors, spec)

    # ------------------------------------------------------- creation
    @classmethod
    def protect(cls, state, policy: HRMPolicy, *,
                roots: Optional[Iterable[str]] = None) -> "MemoryDomain":
        """Classify every leaf of ``state`` into an HRM region, bind each
        region to its policy tier, and materialize the tier sidecars.

        ``state`` may be a single root (a params pytree) or a multi-root
        mapping (``{"params": ..., "opt": ..., "kv_cache": ...}``);
        ``roots`` restricts protection to a subset of top-level keys.
        """
        if roots is not None:
            state = {k: state[k] for k in roots}
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        specs: List[LeafSpec] = []
        cursors: Dict[Tier, int] = {}
        for pos, (path, leaf) in enumerate(flat):
            ok = _supported(leaf)
            region = _classify(path)
            tier = policy.tier_of(region) if ok else Tier.NONE
            rows = ops.words_per_tensor(leaf) // LANES if ok else 0
            if tier is Tier.NONE:
                start = -1
            else:
                start = cursors.get(tier, 0)
                cursors[tier] = start + rows
            specs.append(LeafSpec(
                _path_str(path), pos, region, tier,
                tuple(int(d) for d in getattr(leaf, "shape", ())),
                str(getattr(leaf, "dtype", "float32")), rows, start))
        spec = DomainSpec(policy, tuple(specs), treedef)
        dom = cls(state, {}, {}, spec)
        return dom._encode(spec.row_set(None)) if spec.groups else dom

    # ------------------------------------------------------ accessors
    @property
    def state(self):
        """The protected payload pytree (alias)."""
        return self.payload

    @property
    def policy(self) -> HRMPolicy:
        return self.spec.policy

    def root(self, name: str):
        return self.payload[name]

    def paths(self, protected_only: bool = False) -> List[str]:
        return [s.path for s in self.spec.leaves
                if not protected_only or s.tier is not Tier.NONE]

    def leaf(self, path: str):
        return self._leaves()[self.spec.by_path[path].pos]

    def region_of(self, path: str) -> str:
        return self.spec.by_path[path].region

    def tier_of(self, path: str) -> Tier:
        return self.spec.by_path[path].tier

    def _leaves(self) -> List:
        return list(jax.tree_util.tree_leaves(self.payload))

    def _rebuild(self, leaves, sidecar=None, hard_errors=None
                 ) -> "MemoryDomain":
        payload = jax.tree_util.tree_unflatten(self.spec.treedef, leaves)
        return MemoryDomain(
            payload,
            self.sidecar if sidecar is None else sidecar,
            self.hard_errors if hard_errors is None else hard_errors,
            self.spec)

    # ---------------------------------------------------------- scrub
    def scrub(self, step: Optional[int] = None, *,
              paths: Optional[Iterable[str]] = None
              ) -> Tuple["MemoryDomain", Optional[ScrubReport]]:
        """Verify + correct every protected leaf (or the ``paths`` subset)
        in one tier-batched jit program.

        With ``step`` given, runs only on the policy's scrub schedule and
        returns ``(self, None)`` off-schedule — drop-in for the legacy
        ``Scrubber.maybe_scrub``.
        """
        if step is not None:
            iv = self.spec.policy.scrub_interval
            if iv <= 0 or step % iv != 0:
                return self, None
        return self.scrub_partial(0, slices=1, paths=paths)

    def scrub_partial(self, cursor: int, *, slices: int = 8,
                      paths: Optional[Iterable[str]] = None
                      ) -> Tuple["MemoryDomain", ScrubReport]:
        """Incremental scrub: verify + correct row slice
        ``cursor % slices`` of the selected leaves (1/``slices`` of their
        packed rows, per tier), so calling once per iteration with an
        advancing cursor completes a full scrub pass every ``slices``
        iterations while putting only a sliver of scrub work on each
        iteration's critical path — the scrub/compute-overlap primitive
        behind ``pagerank_scrubbed``/``bfs_scrubbed``.

        Slices cut at packed-row boundaries (never through a codeword);
        within one full cycle every selected row is scrubbed exactly
        once, so ``slices`` consecutive calls correct everything one
        ``scrub()`` would (corrections land as cursor reaches the row).
        Returns (domain, ScrubReport of this slice).
        """
        if not self.spec.groups:
            return self, ScrubReport()
        slices = max(int(slices), 1)
        rows = self.spec.row_set(self.spec.paths_key(paths),
                                 int(cursor) % slices, slices)
        mod, new_sc, corr, unc = _compiled_scrub(self.spec, rows)(
            tuple(self._leaves()), self.sidecar)
        leaves = self._leaves()
        for pos, leaf in mod.items():
            leaves[pos] = leaf
        report = ScrubReport(corrected=dict(corr),
                             detected_uncorrectable=dict(unc))
        return self._rebuild(leaves, sidecar=new_sc), report

    # -------------------------------------------------------- refresh
    def adopt(self, state) -> "MemoryDomain":
        """Swap in an updated payload with the same structure (sidecar is
        stale until ``refresh``)."""
        treedef = jax.tree_util.tree_structure(state)
        if treedef != self.spec.treedef:
            raise ValueError("adopted state structure differs from the "
                             "protected payload")
        return MemoryDomain(state, self.sidecar, self.hard_errors, self.spec)

    def with_leaf(self, path: str, value) -> "MemoryDomain":
        """Replace one payload leaf (its sidecar rows are stale until a
        ``refresh(paths=[path])``) — the single-leaf write primitive the
        sharded peer-copy recovery path builds on."""
        s = self.spec.by_path[path]
        leaves = self._leaves()
        leaves[s.pos] = jnp.asarray(value).reshape(s.shape).astype(
            jnp.dtype(s.dtype))
        return self._rebuild(leaves)

    def refresh(self, state=None, *, paths: Optional[Iterable[str]] = None
                ) -> "MemoryDomain":
        """Re-encode sidecars after legitimate writes (optimizer update,
        clean-copy reload). One batched encode per tier; ``paths`` limits
        the rewrite to the touched leaves."""
        dom = self if state is None else self.adopt(state)
        rows = dom.spec.row_set(dom.spec.paths_key(paths))
        return dom._encode(rows) if rows.tiers else dom

    def refresh_pages(self, pages, state=None) -> "MemoryDomain":
        """Re-encode sidecars after writes confined to index slices
        ``pages`` along the second axis of every protected leaf: the page
        axis of a paged KV pool ``(layers, n_pages, ...)``. Only those
        slices are packed and encoded, and the sidecar comes out as a
        full ``refresh`` would leave it, bit for bit. Each slice has to be
        a whole number of packed rows (``DomainSpec.slices_aligned``),
        else ValueError: the caller chooses the full ``refresh`` then.
        The sidecar is updated in place: this domain's (and any domain's
        that shares it) is spent."""
        dom = self if state is None else self.adopt(state)
        if not dom.spec.groups:
            return dom
        if not dom.spec.slices_aligned():
            raise ValueError("a slice along the second axis is not a whole "
                             "number of packed rows: use refresh()")
        idx = np.asarray(pages, np.int32).reshape(-1)
        if not idx.size:
            return dom
        n1 = min(s.shape[1] for ls in dom.spec.groups.values() for s in ls)
        if idx.min() < 0 or idx.max() >= n1:
            raise IndexError(f"page index out of range [0, {n1})")
        return dom._encode(dom.spec.row_set(None, pages=True),
                           jnp.asarray(idx))

    def _encode(self, rows: RowSet, *idx) -> "MemoryDomain":
        """Encode the sidecar rows of ``rows``, a page set at ``idx``."""
        fn = _compiled_encode(self.spec, rows)
        leaves = tuple(self._leaves())
        sidecar = (fn(leaves) if rows == self.spec.row_set(None)
                   else fn(leaves, self.sidecar, *idx))
        return MemoryDomain(self.payload, sidecar, self.hard_errors,
                            self.spec)

    # ------------------------------------------------------ injection
    def inject(self, rng, n: int = 1, *, hard: bool = False,
               paths: Optional[Iterable[str]] = None,
               multi_bit_fraction: Optional[float] = None,
               adjacent_fraction: Optional[float] = None,
               errors_per_site: int = 1
               ) -> Tuple["MemoryDomain", List[dict]]:
        """Strike ``n`` random protected-or-not leaves with bit flips,
        sampled byte-weighted (errors strike uniformly over physical
        bytes). Hard errors are recorded in the domain's hard-error map
        and re-assert on every ``reassert_hard`` until retired.

        ``multi_bit_fraction``/``adjacent_fraction`` default to the
        policy's ``ErrorModel`` (0.02 multi-bit, half of those adjacent
        bursts) — pass 0.0 explicitly for pure single-bit strikes."""
        em = self.spec.policy.error_model
        if multi_bit_fraction is None:
            multi_bit_fraction = em.multi_bit_fraction
        if adjacent_fraction is None:
            adjacent_fraction = em.adjacent_fraction
        rng = np.random.default_rng(rng)
        if paths is None:
            cands = self.spec.protectable
            weights = self.spec._byte_weights
        else:
            want = set(paths)
            cands = tuple(s for s in self.spec.protectable
                          if s.path in want)
            w = np.array([s.nbytes for s in cands], dtype=np.float64)
            weights = w / w.sum() if w.size and w.sum() > 0 else None
        if not cands:
            return self, []
        leaves = self._leaves()
        hard_map = dict(self.hard_errors)
        events = []
        for _ in range(n):
            s = cands[rng.choice(len(cands), p=weights)]
            plan = InjectionPlan.sample(rng, s.rows * LANES,
                                        errors_per_site, hard,
                                        multi_bit_fraction,
                                        adjacent_fraction)
            leaves[s.pos] = ops.inject_bitflips(
                leaves[s.pos], jnp.asarray(plan.word_idx),
                jnp.asarray(plan.bit_idx))
            if hard:
                wi = jnp.asarray(plan.word_idx)
                bi = jnp.asarray(plan.bit_idx)
                prev = hard_map.get(s.path)
                if prev is not None:
                    wi = jnp.concatenate([prev["word"], wi])
                    bi = jnp.concatenate([prev["bit"], bi])
                hard_map[s.path] = {"word": wi, "bit": bi}
            events.append({"path": s.path, "hard": hard,
                           "words": int((plan.word_idx >= 0).sum())})
        return self._rebuild(leaves, hard_errors=hard_map), events

    def apply_plan(self, path: str, plan: InjectionPlan, *,
                   record_hard: bool = False) -> "MemoryDomain":
        """Apply a pre-sampled injection plan to one leaf (Fig.2 step 2).

        ``record_hard=True`` additionally registers the flips in the
        hard-error map (sticky: re-asserted by ``reassert_hard`` until
        retired) — the trace-replay path uses this for hard events."""
        s = self.spec.by_path[path]
        leaves = self._leaves()
        wi = jnp.asarray(plan.word_idx)
        bi = jnp.asarray(plan.bit_idx)
        leaves[s.pos] = ops.inject_bitflips(leaves[s.pos], wi, bi)
        hard_map = self.hard_errors
        if record_hard:
            hard_map = dict(hard_map)
            prev = hard_map.get(path)
            if prev is not None:
                wi = jnp.concatenate([prev["word"], wi])
                bi = jnp.concatenate([prev["bit"], bi])
            hard_map[path] = {"word": wi, "bit": bi}
        return self._rebuild(leaves, hard_errors=hard_map)

    def reassert_hard(self) -> "MemoryDomain":
        """Re-apply all sticky errors (call after every program write —
        a damaged cell keeps biting)."""
        if not self.hard_errors:
            return self
        leaves = self._leaves()
        for path, err in self.hard_errors.items():
            s = self.spec.by_path[path]
            leaves[s.pos] = ops.inject_bitflips(
                leaves[s.pos], err["word"], err["bit"])
        return self._rebuild(leaves)

    def clear_hard(self, path: Optional[str] = None) -> "MemoryDomain":
        if path is None:
            hard = {}
        else:
            hard = {k: v for k, v in self.hard_errors.items() if k != path}
        return MemoryDomain(self.payload, self.sidecar, hard, self.spec)

    # ------------------------------------------------------- recovery
    def recover(self, report: ScrubReport, *,
                clean_copy: Callable[[str], Any],
                response: Response = Response.RELOAD_CLEAN_COPY,
                strikes: Optional[Dict[str, int]] = None,
                retirement: Optional[RetirementMap] = None,
                retire_after: int = 3,
                needs: Optional[Dict[str, int]] = None
                ) -> Tuple["MemoryDomain", List[dict]]:
        """Software response to detected-uncorrectable errors (Table 2):
        reload flagged leaves from a clean copy (disk checkpoint or peer
        replica), re-encode their sidecar rows, and escalate recurring
        offenders to block retirement — clearing their sticky errors.

        Pass ``needs`` (a precomputed ``report.needs_recovery()``) to
        avoid re-syncing the per-leaf counters from device."""
        if needs is None:
            needs = report.needs_recovery()
        if not needs:
            return self, []
        if response is Response.CONSUME:
            return self, [{"action": "consume", "paths": list(needs)}]
        if response is Response.RESTART:
            raise RestartRequired(str(list(needs)))
        leaves = self._leaves()
        hard_map = dict(self.hard_errors)
        events = []
        for path, n_words in needs.items():
            s = self.spec.by_path[path]
            if strikes is not None:
                strikes[path] = strikes.get(path, 0) + 1
            clean = jnp.asarray(clean_copy(path)).reshape(s.shape).astype(
                jnp.dtype(s.dtype))
            action = ("peer_copy" if response is Response.PEER_COPY
                      else "reload_clean_copy")
            if strikes is not None and strikes[path] >= retire_after:
                if retirement is not None:
                    # retire the actual damaged 512-byte blocks (diff of
                    # the still-corrupted leaf vs its clean replacement),
                    # not the strike count
                    for block in flagged_blocks(leaves[s.pos], clean):
                        retirement.retire(path, block)
                # retired blocks are remapped: their sticky cells stop
                # biting (page-offlining analogue)
                hard_map.pop(path, None)
                action += "+retire"
            leaves[s.pos] = clean
            events.append({"action": action, "path": path,
                           "words": int(n_words)})
        dom = self._rebuild(leaves, hard_errors=hard_map)
        return dom.refresh(paths=list(needs)), events

    # ---------------------------------------------------------- stats
    def stats(self) -> DomainStats:
        region_bytes: Dict[str, int] = {}
        region_tiers: Dict[str, str] = {}
        for s in self.spec.leaves:
            region_bytes[s.region] = region_bytes.get(s.region, 0) + s.nbytes
            region_tiers[s.region] = s.tier.value
        sc_bytes = sum(
            v.size * v.dtype.itemsize
            for tier_buf in self.sidecar.values() for v in tier_buf.values())
        return DomainStats(
            payload_bytes=sum(s.nbytes for s in self.spec.leaves),
            sidecar_bytes=int(sc_bytes),
            n_leaves=len(self.spec.leaves),
            n_protected=sum(1 for s in self.spec.leaves
                            if s.tier is not Tier.NONE),
            n_hard_errors=len(self.hard_errors),
            region_bytes=region_bytes,
            region_tiers=region_tiers)

    def region_profile(self) -> RegionProfile:
        """Measured byte fraction per region (drives the cost model and
        the policy auto-tuner)."""
        stats = self.stats()
        total = max(stats.payload_bytes, 1)
        return RegionProfile({r: b / total
                              for r, b in stats.region_bytes.items()})

    def __repr__(self) -> str:
        tiers = sorted(t.value for t in self.spec.groups)
        return (f"MemoryDomain(policy={self.spec.policy.name!r}, "
                f"leaves={len(self.spec.leaves)}, tiers={tiers}, "
                f"hard_errors={len(self.hard_errors)})")

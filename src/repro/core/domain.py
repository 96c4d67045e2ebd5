"""Unified memory-domain API: one pytree-native HRM object.

The paper's core abstraction is a *memory domain*: a set of memory regions
bound to a reliability tier, scrubbed and recovered as a unit. The seed
exposed that as five loose pieces (``build_sidecar``/``scrub`` free
functions, ``Scrubber``, ``RecoveryManager``, ``Injector``) hand-wired over
a single ``"params"`` root. ``MemoryDomain`` replaces that wiring with one
``jax.tree_util``-registered container owning

    payload          the protected state pytree — multiple roots at once
                     (``params``, ``opt/m``, ``opt/v``, ``kv_cache``)
    sidecar          per-*tier* concatenated ECC/parity buffers
    hard_error_map   live sticky (hard) errors, re-asserted on writes
    policy + plan    static region->tier assignment and buffer layout

and a verb API: ``MemoryDomain.protect(state, policy)``, ``.scrub(step)``,
``.recover(report, ...)``, ``.inject(rng, n, hard=)``, ``.refresh(state,
paths=)``, ``.refresh_pages(pages, state)``, ``.stats()``.

Execution model — tier-grouped batching: instead of the legacy per-leaf
Python loop (one Pallas dispatch per leaf plus an O(n_leaves^2)
``_set_leaf`` re-flatten), the payload is flattened **once**, same-tier
leaves are concatenated into one packed ``(rows, LANES)`` buffer per tier,
one Pallas kernel scrubs the whole tier, per-leaf slices are unpacked, and
the payload is rebuilt with a single ``tree_unflatten``. Per-word ECC math
is position-independent, so results are bit-identical to the legacy path
(``tests/test_domain.py`` asserts this). The whole scrub/encode pass is a
single jit-compiled computation cached per (domain structure, path subset).

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
        self.groups: Dict[Tier, Tuple[int, Tuple[LeafSpec, ...]]] = {
            t: (_round_rows(sum(x.rows for x in ls)), tuple(ls))
            for t, ls in grouped.items()}
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
        """Normalize a path subset into a hashable jit-cache key (in leaf
        order); None selects every protected leaf."""
        if paths is None:
            return None
        want = set(paths)
        return tuple(s.path for s in self.leaves
                     if s.path in want and s.tier is not Tier.NONE)

    def select(self, key: Optional[Tuple[str, ...]]
               ) -> Dict[Tier, Tuple[LeafSpec, ...]]:
        if key is None:
            return {t: g[1] for t, g in self.groups.items()}
        want = set(key)
        out = {}
        for t, (_, ls) in self.groups.items():
            sel = tuple(s for s in ls if s.path in want)
            if sel:
                out[t] = sel
        return out

    def slices_aligned(self) -> bool:
        """Whether every protected leaf's slices along its second axis are
        whole packed rows, so ``refresh_pages`` can encode them alone."""
        return all(_slice_rows(s) for ls in self.select(None).values()
                   for s in ls)


# =====================================================================
# tier-grouped batched kernels (traced helpers + jit caches)
# =====================================================================
def _concat_pad(arrs: List[jax.Array], padded: int) -> jax.Array:
    x = arrs[0] if len(arrs) == 1 else jnp.concatenate(arrs, axis=0)
    pad = padded - x.shape[0]
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


def _gather_rows(buf: jax.Array, sel: Tuple[LeafSpec, ...],
                 padded: int) -> jax.Array:
    return _concat_pad([buf[s.row_start:s.row_start + s.rows] for s in sel],
                       padded)


def _scatter_rows(buf: jax.Array, sel: Tuple[LeafSpec, ...],
                  new: jax.Array) -> jax.Array:
    off = 0
    for s in sel:
        buf = buf.at[s.row_start:s.row_start + s.rows].set(
            new[off:off + s.rows])
        off += s.rows
    return buf


def _gather_packed(leaves, sel: Tuple[LeafSpec, ...], padded: int):
    packed = [ops.pack_words(leaves[s.pos]) for s in sel]
    lo = _concat_pad([p.lo for p in packed], padded)
    hi = _concat_pad([p.hi for p in packed], padded)
    return lo, hi


def _parity_mask(err: jax.Array, like: jax.Array) -> jax.Array:
    """Packed (rows, LANES//8) parity-error bits -> (rows, LANES) bool."""
    bits = (err[..., :, None] >> jnp.arange(8, dtype=jnp.uint32)) & 1
    return bits.reshape(like.shape).astype(jnp.bool_)


def _tier_order(groups: Dict[Tier, Any]) -> List[Tier]:
    return sorted(groups, key=lambda t: t.value)


def _block_rows(padded: int) -> int:
    """Kernel block height for a batched tier buffer. On TPU the 128-row
    VMEM tile is the right block; in interpret mode (CPU) the emulator
    re-materializes every operand per grid step, so one grid step over the
    whole buffer is the fast path."""
    return padded if interpret_mode() else min(BLOCK_ROWS, padded)


def _scrub_tier_buf(tier: Tier, lo, hi, pull, push, bm: int):
    """Run one tier's scrub kernel over a packed (rows, LANES) word window.

    ``pull(name, cast)`` / ``push(name, new, cast)`` read and write the
    sidecar rows matching the window. Returns per-row
    ``(lo2, hi2, corrected, uncorrectable, data_modified)`` —
    ``data_modified=False`` for detect-only PARITY_R, whose counts land in
    the uncorrectable column and whose data/sidecar are left untouched.
    """
    if tier is Tier.SECDED:
        lo2, hi2, ecc2, c, u = secded_scrub_words(
            lo, hi, pull("ecc", jnp.uint32), block_rows=bm)
        push("ecc", ecc2, jnp.uint8)
    elif tier is Tier.DECTED:
        lo2, hi2, ecc2, c, u = dected_scrub_words(
            lo, hi, pull("ecc", jnp.uint32), block_rows=bm)
        push("ecc", ecc2, jnp.uint16)
    elif tier is Tier.BURST:
        lo2, hi2, ecc2, c, u = burst_scrub_words(
            lo, hi, pull("ecc", jnp.uint32), block_rows=bm)
        push("ecc", ecc2, jnp.uint16)
    elif tier is Tier.PARITY_R:
        _err, cnt = parity_check_words(
            lo, hi, pull("par", jnp.uint32), block_rows=bm)
        return lo, hi, jnp.zeros_like(cnt), cnt, False
    elif tier is Tier.MIRROR:
        err, _ = parity_check_words(
            lo, hi, pull("par", jnp.uint32), block_rows=bm)
        mask = _parity_mask(err, lo)
        lo2 = jnp.where(mask, pull("copy_lo"), lo)
        hi2 = jnp.where(mask, pull("copy_hi"), hi)
        c = jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)
        u = jnp.zeros_like(c)
    else:
        raise ValueError(tier)
    return lo2, hi2, c, u, True


@functools.lru_cache(maxsize=None)
def _compiled_scrub(spec: DomainSpec, key: Optional[Tuple[str, ...]]
                    ) -> Callable:
    """One jit program scrubbing every selected leaf, tier-batched.

    fn(leaves_tuple, sidecar) -> (modified {pos: leaf}, new_sidecar,
    corrected {path: n}, detected_uncorrectable {path: n}).
    """
    selected = spec.select(key)

    def fn(leaves, sidecar):
        mod: Dict[int, jax.Array] = {}
        new_sc = {k: dict(v) for k, v in sidecar.items()}
        corr: Dict[str, jax.Array] = {}
        unc: Dict[str, jax.Array] = {}
        for tier in _tier_order(selected):
            sel = selected[tier]
            full_padded, full_specs = spec.groups[tier]
            is_full = len(sel) == len(full_specs)
            padded = full_padded if is_full else _round_rows(
                sum(s.rows for s in sel))
            bm = _block_rows(padded)
            sc = sidecar[tier.value]

            def pull(name, cast=None):
                buf = sc[name]
                out = buf if is_full else _gather_rows(buf, sel, padded)
                return out.astype(cast) if cast is not None else out

            def push(name, new, cast=None):
                new = new.astype(cast) if cast is not None else new
                new_sc[tier.value][name] = new if is_full else \
                    _scatter_rows(sc[name], sel, new[:sum(s.rows
                                                          for s in sel)])

            lo, hi = _gather_packed(leaves, sel, padded)
            lo2, hi2, c, u, wrote = _scrub_tier_buf(tier, lo, hi, pull,
                                                    push, bm)
            off = 0
            for s in sel:
                sl = slice(off, off + s.rows)
                if wrote:
                    mod[s.pos] = ops.unpack_words(
                        ops.Packed(lo2[sl], hi2[sl]), s.shape,
                        jnp.dtype(s.dtype))
                    corr[s.path] = jnp.sum(c[sl])
                unc[s.path] = jnp.sum(u[sl])
                off += s.rows
        return mod, new_sc, corr, unc

    return _jit_named(fn, f"{spec.kind}_scrub")


@functools.lru_cache(maxsize=None)
def _compiled_scrub_rows(spec: DomainSpec, key: Optional[Tuple[str, ...]],
                         idx: int, slices: int) -> Callable:
    """One jit program scrubbing row slice ``idx`` of ``slices`` over the
    selection — the incremental-scrub cursor's compiled step.

    The slice is taken per tier over the *virtual* concatenated row space
    of the selected leaves (so every tier advances each call and finishes
    together after ``slices`` calls), cut at packed-row boundaries: a row
    holds whole 64-bit words of one leaf, so slicing never splits an ECC
    codeword. Leaves overlapping the window are spliced at row
    granularity — the corrected rows replace the leaf's packed rows and
    the leaf is rebuilt, bit-identical outside the window.
    """
    selected = spec.select(key)

    def fn(leaves, sidecar):
        mod: Dict[int, jax.Array] = {}
        new_sc = {k: dict(v) for k, v in sidecar.items()}
        corr: Dict[str, jax.Array] = {}
        unc: Dict[str, jax.Array] = {}
        for tier in _tier_order(selected):
            sel = selected[tier]
            total = sum(s.rows for s in sel)
            lo_r = (idx * total) // slices
            hi_r = ((idx + 1) * total) // slices
            if hi_r <= lo_r:
                continue
            # leaf pieces overlapping the window, in leaf-local rows
            pieces = []
            off = 0
            for s in sel:
                a, b = max(lo_r - off, 0), min(hi_r - off, s.rows)
                if a < b:
                    pieces.append((s, a, b))
                off += s.rows
            padded = _round_rows(hi_r - lo_r)
            bm = _block_rows(padded)
            sc = sidecar[tier.value]
            packed = {s.path: ops.pack_words(leaves[s.pos])
                      for s, _, _ in pieces}
            lo = _concat_pad([packed[s.path].lo[a:b]
                              for s, a, b in pieces], padded)
            hi = _concat_pad([packed[s.path].hi[a:b]
                              for s, a, b in pieces], padded)

            def pull(name, cast=None):
                out = _concat_pad(
                    [sc[name][s.row_start + a:s.row_start + b]
                     for s, a, b in pieces], padded)
                return out.astype(cast) if cast is not None else out

            def push(name, new, cast=None):
                new = new.astype(cast) if cast is not None else new
                buf = new_sc[tier.value][name]
                o = 0
                for s, a, b in pieces:
                    buf = buf.at[s.row_start + a:s.row_start + b].set(
                        new[o:o + (b - a)])
                    o += b - a
                new_sc[tier.value][name] = buf

            lo2, hi2, c, u, wrote = _scrub_tier_buf(tier, lo, hi, pull,
                                                    push, bm)
            o = 0
            for s, a, b in pieces:
                sl = slice(o, o + (b - a))
                if wrote:
                    p = packed[s.path]
                    mod[s.pos] = ops.unpack_words(
                        ops.Packed(p.lo.at[a:b].set(lo2[sl]),
                                   p.hi.at[a:b].set(hi2[sl])),
                        s.shape, jnp.dtype(s.dtype))
                    corr[s.path] = jnp.sum(c[sl])
                unc[s.path] = jnp.sum(u[sl])
                o += b - a
        return mod, new_sc, corr, unc

    return _jit_named(fn, f"{spec.kind}_scrub_slice")


def _encode_tier(tier: Tier, lo, hi, bm: int) -> Dict[str, jax.Array]:
    """One tier's fresh sidecar buffers for a packed (rows, LANES) window."""
    if tier is Tier.SECDED:
        return {"ecc": secded_encode_words(
            lo, hi, block_rows=bm).astype(jnp.uint8)}
    if tier is Tier.DECTED:
        return {"ecc": dected_encode_words(
            lo, hi, block_rows=bm).astype(jnp.uint16)}
    if tier is Tier.BURST:
        return {"ecc": burst_encode_words(
            lo, hi, block_rows=bm).astype(jnp.uint16)}
    if tier is Tier.PARITY_R:
        return {"par": parity_encode_words(
            lo, hi, block_rows=bm).astype(jnp.uint8)}
    if tier is Tier.MIRROR:
        return {"copy_lo": lo, "copy_hi": hi,
                "par": parity_encode_words(
                    lo, hi, block_rows=bm).astype(jnp.uint8)}
    raise ValueError(tier)


@functools.lru_cache(maxsize=None)
def _compiled_encode(spec: DomainSpec, key: Optional[Tuple[str, ...]]
                     ) -> Callable:
    """One jit program (re-)encoding sidecar buffers for the selection.

    Full selection: fn(leaves) -> sidecar. Subset: fn(leaves, sidecar) ->
    sidecar with only the selected rows rewritten.
    """
    selected = spec.select(key)

    def encode_tier(tier, leaves, sel, padded):
        lo, hi = _gather_packed(leaves, sel, padded)
        return _encode_tier(tier, lo, hi, _block_rows(padded))

    if key is None:
        def fn_full(leaves):
            sc = {}
            for tier in _tier_order(selected):
                padded, _ = spec.groups[tier]
                sc[tier.value] = encode_tier(tier, leaves, selected[tier],
                                             padded)
            return sc
        return _jit_named(fn_full, f"{spec.kind}_encode")

    def fn_partial(leaves, sidecar):
        new_sc = {k: dict(v) for k, v in sidecar.items()}
        for tier in _tier_order(selected):
            sel = selected[tier]
            total = sum(s.rows for s in sel)
            fresh = encode_tier(tier, leaves, sel, _round_rows(total))
            for name, new in fresh.items():
                new_sc[tier.value][name] = _scatter_rows(
                    sidecar[tier.value][name], sel, new[:total])
        return new_sc

    return _jit_named(fn_partial, f"{spec.kind}_encode_rows")


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


@functools.lru_cache(maxsize=None)
def _compiled_encode_slices(spec: DomainSpec) -> Callable:
    """One jit program re-encoding the sidecar rows of index slices along
    the second axis of every protected leaf (each slice a whole number of
    packed rows: ``DomainSpec.slices_aligned``).

    fn(leaves, sidecar, idx) -> sidecar, the sidecar donated so the
    scatter updates its buffers in place. Only ``leaf[:, idx]`` is gathered
    and packed, and its fresh code rows are scattered to the slices' rows;
    a repeated index writes the same rows twice. The gather is cheap where
    the second axis is not the most minor in device memory: see
    ``serve.paged_kv`` for the pool layout that keeps it so on a TPU.
    Every tier's code is per 64-bit word, so the result is the full
    encode's, bit for bit, wherever only those slices changed. It is the
    write-path encode narrowed, and lowers under the same name.
    """
    selected = spec.select(None)

    def fn(leaves, sidecar, idx):
        new_sc = {k: dict(v) for k, v in sidecar.items()}
        for tier in _tier_order(selected):
            los, his, at = [], [], []
            for s in selected[tier]:
                r = _slice_rows(s)
                n = s.shape[0] * idx.shape[0] * r
                p = ops.pack_words(leaves[s.pos][:, idx])
                los.append(p.lo[:n])
                his.append(p.hi[:n])
                first = (jnp.arange(s.shape[0], dtype=jnp.int32)[:, None]
                         * s.shape[1] + idx[None, :]) * r + s.row_start
                at.append((first[:, :, None]
                           + jnp.arange(r, dtype=jnp.int32)).reshape(-1))
            at = jnp.concatenate(at)
            total = at.shape[0]
            padded = _round_rows(total)
            fresh = _encode_tier(tier, _concat_pad(los, padded),
                                 _concat_pad(his, padded),
                                 _block_rows(padded))
            for name, new in fresh.items():
                new_sc[tier.value][name] = \
                    sidecar[tier.value][name].at[at].set(new[:total])
        return new_sc

    return _jit_named(fn, f"{spec.kind}_encode", donate_argnums=(1,))


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
        leaves = tuple(leaf for _, leaf in flat)
        sidecar = _compiled_encode(spec, None)(leaves) if spec.groups else {}
        return cls(state, sidecar, {}, spec)

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
        if not self.spec.groups:
            return self, ScrubReport()
        key = self.spec.paths_key(paths)
        mod, new_sc, corr, unc = _compiled_scrub(self.spec, key)(
            tuple(self._leaves()), self.sidecar)
        leaves = self._leaves()
        for pos, leaf in mod.items():
            leaves[pos] = leaf
        report = ScrubReport(corrected=dict(corr),
                             detected_uncorrectable=dict(unc))
        return self._rebuild(leaves, sidecar=new_sc), report

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
        if slices <= 1:
            return self.scrub(paths=paths)
        if not self.spec.groups:
            return self, ScrubReport()
        key = self.spec.paths_key(paths)
        mod, new_sc, corr, unc = _compiled_scrub_rows(
            self.spec, key, int(cursor) % slices, int(slices))(
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
        if not dom.spec.groups:
            return dom
        key = dom.spec.paths_key(paths)
        leaves = tuple(dom._leaves())
        if key is None:
            sidecar = _compiled_encode(dom.spec, None)(leaves)
        else:
            if not key:
                return dom
            sidecar = _compiled_encode(dom.spec, key)(leaves, dom.sidecar)
        return MemoryDomain(dom.payload, sidecar, dom.hard_errors, dom.spec)

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
        n1 = min(s.shape[1] for ls in dom.spec.select(None).values()
                 for s in ls)
        if idx.min() < 0 or idx.max() >= n1:
            raise IndexError(f"page index out of range [0, {n1})")
        sidecar = _compiled_encode_slices(dom.spec)(
            tuple(dom._leaves()), dom.sidecar, jnp.asarray(idx))
        return MemoryDomain(dom.payload, sidecar, dom.hard_errors, dom.spec)

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

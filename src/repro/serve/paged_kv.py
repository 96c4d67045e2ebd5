"""Paged KV cache: fixed-size pages, a host-side free-list allocator, and
device pools that register as their own ``MemoryDomain`` root.

Layout: named pools of ``(n_layers, n_pages, ...)``, their leaves chosen
by the attention kind (``pool_shapes``), each page lane-dense: a TPU lays
out an array whose trailing axes are narrower than its 128-lane tile
(heads of 64) with the page axis in lanes, and then taking one page
reads every tile of the pool.

- grouped-query attention: ``k`` and ``v``, ``(L, pages, page_size,
  n_kv_heads * head_dim)``, the heads and their width merged;
- latent attention: ``c_kv``, ``(L, pages, page_size, kv_lora_rank)``,
  the normed latent, and ``k_pe``, the rotary key shared by the heads,
  ``128 // qk_rope_head_dim`` tokens to a 128-lane row: ``(L, pages,
  page_size * qk_rope_head_dim // 128, 128)``, the row-major bytes of
  ``(L, pages, page_size, qk_rope_head_dim)`` (``pack_rope``).

Page 0 is the reserved *null* page — page-table slots
that a request has not grown into yet point at it, and decode steps of
inactive scheduler slots write their garbage K/V there. The null page is
only ever read at attention positions past a slot's current length, where
the causal/validity mask zeroes its weight exactly, so its contents never
reach an output.

The pools are the Fig. 4 "most error-tolerant, largest" region: the
engine wraps them in a second ``MemoryDomain`` (root ``kv_cache``) so the
KV pages can run under a cheap tier (none/parity/SEC-DED) while the
params domain stays strongly protected.

Allocation is per-request and up-front: a request's full footprint
(prompt + max_new positions, rounded up to whole pages) is reserved at
admission, so an admitted request can never deadlock mid-decode waiting
for pages. ``check_invariants`` asserts the two safety properties the
tests pin: no page is mapped by two slots (no cross-request KV aliasing)
and the free list and page tables exactly partition the pool (no leaks).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.common import dtype_of

NULL_PAGE = 0
ROW_LANES = 128                 # a TPU tile's lanes


def rope_tokens_per_row(cfg: ModelConfig) -> int:
    """Tokens of the latent cache's rotary key packed into one row."""
    dr = cfg.mla.qk_rope_head_dim
    if ROW_LANES % dr:
        raise ValueError(f"qk_rope_head_dim {dr} does not divide "
                         f"{ROW_LANES} lanes")
    return ROW_LANES // dr


def pool_shapes(cfg: ModelConfig, n_pages: int, page_size: int
                ) -> Dict[str, Tuple[int, ...]]:
    """The pools' names and shapes for the attention kind."""
    lead = (cfg.n_layers, n_pages)
    if cfg.mla:
        g = rope_tokens_per_row(cfg)
        if page_size % g:
            raise ValueError(f"a page of {page_size} tokens is not whole "
                             f"rows of {g} rotary keys")
        return {"c_kv": lead + (page_size, cfg.mla.kv_lora_rank),
                "k_pe": lead + (page_size // g, ROW_LANES)}
    width = cfg.n_kv_heads * cfg.head_dim
    return {"k": lead + (page_size, width), "v": lead + (page_size, width)}


def pack_rope(k_pe, g: int):
    """(..., tokens, dr) rotary keys -> (..., tokens // g, g dr) rows."""
    *lead, t, dr = k_pe.shape
    return k_pe.reshape(*lead, t // g, g * dr)


class PagedKVCache:
    def __init__(self, cfg: ModelConfig, *, n_pages: int, page_size: int,
                 slots: int, max_pages_per_slot: int):
        if cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"paged KV serving supports attention-cache families "
                f"(dense/moe/vlm), not {cfg.family!r}")
        if n_pages < 2:
            raise ValueError("need at least one real page beside the null "
                             "page")
        cdt = dtype_of(cfg.compute_dtype)
        self.pools: Dict[str, jnp.ndarray] = {
            name: jnp.zeros(shape, cdt)
            for name, shape in pool_shapes(cfg, n_pages, page_size).items()}
        self.page_size = page_size
        self.n_pages = n_pages
        self.slots = slots
        self.max_pages_per_slot = max_pages_per_slot
        # LIFO free list over real pages; page 0 stays out as the null page
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self.table = np.full((slots, max_pages_per_slot), NULL_PAGE,
                             np.int32)
        self._owner: Dict[int, int] = {}          # page -> slot

    # ------------------------------------------------------------- sizing
    def pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def can_admit(self, tokens: int) -> bool:
        n = self.pages_needed(tokens)
        return n <= self.max_pages_per_slot and n <= self.free_pages

    # --------------------------------------------------------- allocation
    def alloc(self, slot: int, tokens: int) -> np.ndarray:
        """Reserve the full page footprint for one request in ``slot``."""
        n = self.pages_needed(tokens)
        if n > self.max_pages_per_slot:
            raise ValueError(f"request needs {n} pages > max_pages_per_slot"
                             f"={self.max_pages_per_slot}")
        if n > len(self._free):
            raise MemoryError(f"out of KV pages: need {n}, "
                              f"free {len(self._free)}")
        if (self.table[slot] != NULL_PAGE).any():
            raise RuntimeError(f"slot {slot} already holds pages")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._owner[p] = slot
        self.table[slot, :n] = pages
        return np.asarray(pages, np.int32)

    def release(self, slot: int) -> List[int]:
        """Return every page mapped by ``slot`` to the free list."""
        pages = [int(p) for p in self.table[slot] if p != NULL_PAGE]
        for p in pages:
            assert self._owner.pop(p) == slot
            self._free.append(p)
        self.table[slot] = NULL_PAGE
        return pages

    def release_all(self) -> None:
        for s in range(self.slots):
            self.release(s)

    # ------------------------------------------------------------- device
    def device_table(self) -> jnp.ndarray:
        return jnp.asarray(self.table)

    def adopt_pools(self, pools: Dict[str, jnp.ndarray]) -> None:
        """Take updated device pools back from a jitted step."""
        assert pools.keys() == self.pools.keys()
        self.pools = dict(pools)

    # --------------------------------------------------------- invariants
    def check_invariants(self) -> None:
        mapped = [int(p) for row in self.table for p in row
                  if p != NULL_PAGE]
        assert len(mapped) == len(set(mapped)), \
            "cross-request KV page aliasing"
        assert NULL_PAGE not in self._free, "null page on the free list"
        assert not (set(mapped) & set(self._free)), \
            "page both mapped and free"
        assert len(mapped) + len(self._free) == self.n_pages - 1, \
            "page leak: mapped + free != pool"
        assert set(self._owner) == set(mapped), "owner map out of sync"

"""Operations and bytes that a PageRank iteration needs, counted from the
graph's size and the cell's policy.

Push: each edge reads its source's contribution and adds it to its
destination (8 bytes: two 4-byte words), each vertex reads its rank and
out-degree and writes its new rank (8 bytes counted, as the yardstick
takes it); 2 FLOPs per edge. Protection per iteration: the new ranks'
parity written once (an encode reads the ranks and writes the sidecar),
and one ``scrub_slices``-th of the protected payload and its sidecar read
once (topology under SEC-DED, ranks under parity).
"""
from __future__ import annotations

from typing import Dict

SIDECAR_PER_BYTE = {"none": 0.0, "parity_r": 1.0 / 64, "secded": 8.0 / 64}


def push_need(edges: int, vertices: int) -> Dict[str, float]:
    return {"flops": 2.0 * edges, "bytes": 8.0 * edges + 8.0 * vertices}


def protected_bytes(topology_bytes: int, rank_bytes: int,
                    tiers: Dict[str, str]) -> Dict[str, float]:
    """(payload, sidecar) bytes of the protected regions."""
    out = {"payload": 0.0, "sidecar": 0.0}
    for region, b in (("topology", topology_bytes), ("rank", rank_bytes)):
        side = SIDECAR_PER_BYTE[tiers.get(region, "none")]
        if side:
            out["payload"] += b
            out["sidecar"] += b * side
    return out


def ecc_need_bytes(topology_bytes: int, rank_bytes: int,
                   tiers: Dict[str, str], scrub_slices: int) -> float:
    """Bytes of one iteration's ECC kernels: the rank encode and one slice
    of the scrub."""
    prot = protected_bytes(topology_bytes, rank_bytes, tiers)
    enc = rank_bytes * (1 + SIDECAR_PER_BYTE[tiers.get("rank", "none")])
    return enc + (prot["payload"] + prot["sidecar"]) / scrub_slices


def iteration_need_seconds(edges: int, vertices: int, topology_bytes: int,
                           rank_bytes: int, tiers: Dict[str, str],
                           scrub_slices: int, peaks: dict) -> float:
    push = push_need(edges, vertices)
    nbytes = push["bytes"] + ecc_need_bytes(topology_bytes, rank_bytes,
                                            tiers, scrub_slices)
    return max(push["flops"] / peaks["flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])

"""Least bytes that the clustering work must move through HBM.

A roofline share is the least time the chip could take over the time it
took, so each function here is a lower bound: it never counts a byte that
some implementation could avoid moving.

* :func:`neighbor_min_call_bytes` — one call of the ``neighbor_min`` ELL
  kernel at a packed ``(B, R, W)`` shape. Its inputs arrive in HBM and a
  separate kernel call cannot keep them on chip from the last call, so it
  reads the int32 ELL, the int32 rank rows and the bool activity rows once
  and writes its int32 ``(B, R)`` minima once. Gathers through the state
  rows are not counted: a row of ``R + 1`` words fits on chip. This is
  ``launch/roofline.py``'s ``ell_kernel_bytes`` without its gathered words,
  which are no HBM traffic when the row is held in VMEM.
* :func:`graph_bytes` — one request of the bucket program, whatever layout
  or loop implements it: its kept (eligible-induced) undirected edges as
  two int32 ids each, the ``k`` samples' int32 ranks of its ``n`` vertices,
  and its int32 labels written once.
"""

from __future__ import annotations

INT32 = 4
BOOL = 1


def neighbor_min_call_bytes(b: int, r: int, w: int) -> int:
    return (INT32 * b * r * w                 # ELL read once
            + INT32 * b * (r + 1)             # rank rows
            + BOOL * b * (r + 1)              # activity rows
            + INT32 * b * r)                  # minima written


def graph_bytes(n: int, kept_edges: int, k: int) -> int:
    return INT32 * (2 * kept_edges + k * n + n)

"""Bytes and operations one iteration must move, from shapes alone (copied
from ``tenzing_tpu/bench/roofline.py`` ``halo_cost`` / ``spmv_cost``).  A
builder names the function for its configuration; a roofline share divides
the result by a measured device time, so these can only be counted too high
by changing this file, which later PRs cannot."""

from __future__ import annotations


def halo_cost(nq: int, lx: int, ly: int, lz: int, radius: int,
              bytes_per_el: int = 4) -> dict:
    """Six-face halo exchange: zero FLOPs.  Per face the device reads the
    interior face and writes the pack buffer, then reads the received buffer
    and writes the ghost shell: four face-sizes of HBM traffic.  The transfer
    between the two buffers is not counted (its path is the search's
    choice), so the figure is a floor."""
    face_cells = 2 * (lx * ly + ly * lz + lx * lz) * radius * nq
    return {"flops": 0.0, "hbm_bytes": 4.0 * face_cells * bytes_per_el}


def spmv_cost(m: int, nnz: int, bytes_per_el: int = 4) -> dict:
    """y = A x over stored elements: 2 FLOPs each; HBM reads value, column
    index and gathered x per stored element, and per row writes y and reads
    one 4-byte offset."""
    return {"flops": 2.0 * nnz,
            "hbm_bytes": float(nnz) * (2 * bytes_per_el + 4)
            + float(m) * (bytes_per_el + 4)}

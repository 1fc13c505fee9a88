"""Operations and bytes of the serving programs, and the time the chip's
peaks (``peaks.json``) allow them: what the ``*_roofline`` readers divide.

L support rows, M features, float32 operands (4 bytes). Operations are
counted at the bfloat16 peak, above anything float32 dots reach, so each
roofline time is a lower bound on the program's.
"""

from __future__ import annotations

from typing import Optional


def peaks(kind: str) -> Optional[dict]:
    """The chip's peaks by device kind; None for a kind ``peaks.json``
    does not list (the CPU)."""
    from bench import run
    try:
        return run.peaks_for(kind)
    except run.Refused:
        return None


def kernel_means_ops(l: int, m: int) -> float:
    """The artifact's kernel-mean pass: every (row, support row) distance
    dot, 2 L^2 M."""
    return 2.0 * l * l * m


def kernel_means_bytes(l: int, m: int, blocks: int) -> float:
    """The support read once, and once more for each row block."""
    return 4.0 * l * m * (1 + blocks)


def project_ops(rows: int, l: int, m: int) -> float:
    """Projection of ``rows`` slab rows (queries and padding): their
    distance dots against the support, 2 rows L M."""
    return 2.0 * rows * l * m


def project_bytes(slabs: int, rows: int, l: int, m: int) -> float:
    """Each program call reads the support; each slab row is read once."""
    return 4.0 * (slabs * l * m + rows * m)


def roofline_s(ops: float, nbytes: float, peak: dict) -> float:
    return max(ops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

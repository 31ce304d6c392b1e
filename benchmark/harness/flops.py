"""The roofline, common to every family. The operations and bytes an
algorithm needs are counted from shapes alone by the cell's family
(``manifest.load_family``), with the benchmark, so that no later PR can
move them; the peaks are ``peaks.py``'s."""

from __future__ import annotations


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds the chip could take, which bound applies)."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")

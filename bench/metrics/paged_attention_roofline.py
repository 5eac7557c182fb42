"""``paged_attention``'s share of its roofline in the traced window
(``bench/kernels/paged_attention.py`` counts, ``bench/peaks.json``). XLA
builds the kernel's keys and values in VMEM, in the fusions that gather
the resident set; those fusions read them from HBM and count as the
kernel's time."""
from bench.lib import stats


def read(run):
    return stats.roofline_pct(run, "paged_attention")

"""``page_scores``'s share of its roofline in the traced window
(``bench/kernels/page_scores.py`` counts, ``bench/peaks.json``). XLA
copies the page summaries into VMEM before the kernel; that copy reads
them from HBM and counts as the kernel's time."""
from bench.lib import stats


def read(run):
    return stats.roofline_pct(run, "page_scores")

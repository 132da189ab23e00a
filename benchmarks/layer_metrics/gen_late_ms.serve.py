"""The benchmark's generator: the 95th percentile of (actual send -
due time) over the window's requests, in ms. A starved generator must
not read as a fast server. Source: the generator's own stamps. Moves
serve_p95_ms.
"""

from harness import percentile


def read(run):
    late = run.samples.get("gen_late_ms")
    if not late:
        return None
    return percentile(late, 0.95)

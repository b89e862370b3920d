"""Median ``compute`` span of one layer on the program's ``<req>/wall``
tracks (``ModelRunner._layer_packed``, ended by ``block_until_ready``)."""
from chipbench.stats import nearest_rank


def read(run):
    xs = [1e3 * s.dur_s for s in run.spans
          if s.track.endswith("/wall") and s.name == "compute"
          and "layer" in s.args]
    return nearest_rank(xs, 50) if xs else None

"""Median ``dequant`` span on the program's ``<req>/wall`` tracks: one
layer's packed payload uploaded to the device
(``kv_chunks.layer_payload_to_packed_kv``).  The span ends when the upload
is enqueued, so part of the transfer can fall in the next ``compute``."""
from chipbench.stats import nearest_rank


def read(run):
    xs = [1e3 * s.dur_s for s in run.spans
          if s.track.endswith("/wall") and s.name == "dequant"]
    return nearest_rank(xs, 50) if xs else None

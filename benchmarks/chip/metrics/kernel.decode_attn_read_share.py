"""Live-block occupancy of the decode attention: the K and V bytes of the
live blocks the kernel is asked to read (each decoding row's blocks by
the kernel's own rule, ``decode_attention.live_blocks``), summed over the
window's decode calls (mixed iterations included), over the bytes of the
whole stacked cache times those calls. It is what attention would read
were it to read only live blocks, as a share of reading every slot at
``max_len``; it follows the traffic, not what the kernel fetched. Read
from the program's own iteration records (``repro.obs.RECORDER``), one
per iteration in ``run.iterations``. None for a program without that
count."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    recs = [r for r in obs.RECORDER.match(run.iterations)
            if getattr(r, "kv_read_bytes", None) is not None]
    if not recs:
        return None
    return (sum(r.kv_read_bytes for r in recs)
            / sum(r.kv_cache_bytes for r in recs))

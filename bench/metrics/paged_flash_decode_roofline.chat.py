"""Share of its roofline that ``paged_flash_decode`` reached in the window:
the least time the chip needs for the bytes and FLOPs of the pages each call
read (``bench/costs/paged_flash_decode.py``, from the slot depths at each
decode tick), over the kernel's device time, in %.  The bound that applies
(bytes or FLOPs) is the larger least time; at these depths it is bytes."""

from bench.costs import paged_flash_decode as pfd
from bench.stats import tick_ends
from bench.trace_reduce import op_time


def read(rec, tr):
    r, arch, c = rec["record"], rec["arch"], rec["cfg_json"]["serve"]
    ends = tick_ends(r)
    w0, w1 = r["window"]
    depths = {}  # decode tick -> keys attended per slot
    for x in r["requests"]:
        for j, t in enumerate(x.get("token_ticks") or []):
            if j and w0 <= ends[t] < w1:
                depths.setdefault(t, []).append(x["prompt_len"] + j)
    page = rec["page_size"]
    least = 0.0
    for ds in depths.values():
        flops, nbytes = pfd.cost(ds, num_slots=c["num_slots"], heads=arch.heads,
                                 kv_heads=arch.kv_heads, head_dim=arch.head_dim,
                                 page_size=page, max_pages=c["max_seq"] // page)
        least += arch.layers * max(flops / rec["peak_flops"], nbytes / rec["peak_bw"])
    t = op_time(tr, r"^paged_flash_decode")
    return 100.0 * least / t if t > 0 and depths else None

"""Share of peak bf16 FLOP/s that the prefill program reaches: the FLOPs of
one prefill (from the configuration's shapes, computed by its reference
module's ``prefill_flops``) over the prefill program's mean device time in
the trace, over the device's peak bf16 FLOP/s.  None where the reference
has no ``prefill_flops``."""
import importlib

PROGRAM = "prefill_step"


def read(record):
    t = record.get("trace")
    if not t or not record.get("peaks"):
        return None
    ref = importlib.import_module(f"bench.reference.{record['family']}")
    flops = getattr(ref, "prefill_flops", None)
    runs = [p for name, p in t["programs"].items() if PROGRAM in name]
    count = sum(p["count"] for p in runs)
    if flops is None or not count:
        return None
    seconds = sum(p["seconds"] for p in runs) / count
    tr = record["traffic"]
    need = flops(record["model"], tr["batch"], tr["prompt_len"])
    return 100.0 * need / seconds / record["peaks"]["bf16_flops_per_s"]

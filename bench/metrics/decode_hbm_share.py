"""Share of peak HBM bandwidth that the decode program reaches: the bytes
one decode call must move (from the configuration's shapes, computed by its
reference module's ``decode_bytes``) over the decode program's mean device
time in the trace, over the device's peak bytes/s."""
import importlib

PROGRAM = "decode_step"


def read(record):
    t = record.get("trace")
    if not t or not record.get("peaks"):
        return None
    runs = [p for name, p in t["programs"].items() if PROGRAM in name]
    count = sum(p["count"] for p in runs)
    if not count:
        return None
    seconds = sum(p["seconds"] for p in runs) / count
    tr = record["traffic"]
    ref = importlib.import_module(f"bench.reference.{record['family']}")
    need = ref.decode_bytes(record["model"], tr["batch"], tr["prompt_len"],
                            tr["new_tokens"])
    return 100.0 * need / seconds / record["peaks"]["hbm_bytes_per_s"]

"""``decode_hbm_share`` in the zamba2 cell: the share of peak HBM bandwidth
that the decode program reaches, by the formula of
``bench/metrics/decode_hbm_share.py``, loaded as the harness loads it."""
from bench import harness


def read(record):
    return harness._load_reader("decode_hbm_share")(record)

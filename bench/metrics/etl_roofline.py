"""The ETL apply program's share of its roofline: the least time the
chip's HBM bandwidth allows for the bytes the pipeline must move per batch
(``bench/work.py``), over the measured device time per batch."""

from bench.metrics import etl_device_ms


def read(run):
    ms = etl_device_ms.read(run)
    if not ms:
        return None
    least_s = run["work"]["etl_bytes_per_batch"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)

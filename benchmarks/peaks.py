"""Published peaks by ``device_kind``. A device that is not in the table
is an error, never a default.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM.
(Copied from ``bench._PEAK_TFLOPS``, which has the FLOP/s only; the
other kinds of that table come back with the first cell that runs on
them.)
"""

PEAKS = (
    ("v5 lite", {"flops": 197.0e12, "hbm_bytes_per_s": 819.0e9,
                 "hbm_bytes": 16.0e9}),
    ("v5e", {"flops": 197.0e12, "hbm_bytes_per_s": 819.0e9,
             "hbm_bytes": 16.0e9}),
)


def peaks_of(device_kind):
    kind = str(device_kind).lower()
    for key, row in PEAKS:
        if key in kind:
            return row
    raise ValueError(f"no peaks on record for device_kind {device_kind!r}; "
                     "add it to benchmarks/peaks.py with its source")

"""Peak device memory of the process on its fullest chip,
``memory_stats()["peak_bytes_in_use"]`` after the window, in GB.  It
includes the float32 reference's one-layer transients of the set-up."""

NAME = "peak_hbm_gb"
UNIT = "GB"
LAYER = "device"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    if run.device["platform"] != "tpu":
        return None
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None

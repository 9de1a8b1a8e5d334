"""Peak device memory of the training run (``peak_bytes_in_use`` on the
fullest chip), in GB of 1e9 bytes."""


def read(run):
    b = run.get("memory_peak_bytes")
    return b / 1e9 if b else None

"""Peak device memory of the fullest chip after the window
(``device.memory_stats()['peak_bytes_in_use']``), in GB of 1e9 bytes."""


def read(run):
    peak = run.get('memory_peak_bytes')
    return peak / 1e9 if peak else None

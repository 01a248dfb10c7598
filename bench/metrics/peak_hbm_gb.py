"""Peak device memory on the fullest chip, GB, read after the window:
``memory_stats()`` ``peak_bytes_in_use`` plus ``peak_bytes_reserved``
(the executables' temporaries, which the TPU allocator reserves apart
from the buffers in use)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9

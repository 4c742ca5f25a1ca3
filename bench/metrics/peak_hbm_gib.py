"""peak_hbm_gib: the most bytes that the device's buffers held in the
window (memory_stats bytes_in_use, sampled every millisecond): the
resident data plus the window's results and copies in flight. The
allocator's own peak is left out, since it also holds set-up's warm-up."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 2 ** 30

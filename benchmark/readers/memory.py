"""Peak device memory after the window, in GB (1e9 bytes), the fullest of
the devices the cell uses, from `device.memory_stats()`."""


def peak_bytes(devices):
    """The TPU runtime keeps two books: `peak_bytes_in_use` counts live
    arrays (weights, optimizer state, batches), and the temporaries of a
    running program live in a region it reserves apart (`peak_bytes_reserved`;
    for the transformer step 10.67 GB against the 10.72 GB of XLA's own
    `memory_analysis().temp_size_in_bytes`, chip run, PR 24). What the chip
    held at its fullest is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def read(ctx):
    peak = peak_bytes(ctx["system"].devices)
    return None if peak is None else peak / 1e9

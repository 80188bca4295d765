"""Peak bytes in use on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GB."""
name, unit = "device.peak_hbm_gb", "GB"
layer, moves, source = "device (TPU v5e)", "tokens_per_s", "program_counter"


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None

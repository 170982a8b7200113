"""``memory_stats()["peak_bytes_in_use"]`` after the window, the largest
over devices."""

LAYER = "device"
UNIT = "GiB"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return None if peak is None else peak / 2 ** 30

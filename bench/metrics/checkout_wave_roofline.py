"""Share of the HBM roofline reached by the ``jit_checkout_wave`` programs
in the traced window: the least time the chip's HBM bandwidth allows for
the bytes the requests' semantics require, over the device time of those
programs.

The bytes are counted from the semantics, not from the program's layout:
each wave's unique versions' rows, ``n_attrs`` values of ``itemsize``
bytes each, read once and written once.  Lane padding, tile padding and
duplicate requests in a wave are the program's choice and count nothing."""

from bench.spec import peaks

MODULE = "jit_checkout_wave"


def semantic_bytes(waves, size, n_attrs: int, itemsize: int) -> int:
    """Bytes a wave gather must move: unique versions' rows, read + write."""
    rows = sum(size(v) for vids in waves for v in set(vids))
    return 2 * rows * n_attrs * itemsize


def read(ctx):
    if ctx.trace is None or not ctx.trace.module_s.get(MODULE):
        return None
    nbytes = semantic_bytes(ctx.waves, ctx.size, ctx.n_attrs, ctx.itemsize)
    bound_s = nbytes / peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return bound_s / ctx.trace.module_s[MODULE] * 100

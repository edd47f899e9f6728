"""Share of the gathered tiles in the window that the launch ladder added
(``kernels.ops.launch_tiles`` pads a gather's plan to the next rung of a
fixed tile ladder so waves share a compiled gather): ``pad_tiles`` over
``tiles + pad_tiles`` of ``CheckoutStats``, in %.  The extra gather and
device->host copy the ladder costs; a program without the ladder has no
such counters and reports nothing."""


def read(ctx):
    pad = ctx.stats.get("pad_tiles")
    total = ctx.stats.get("tiles", 0) + (pad or 0)
    if pad is None or not total:
        return None
    return pad / total * 100

"""Wall time per scheduling decision: the measured window's whole wall
time over every decision the engine made in it."""


def read(ctx):
    made = ctx["at_end"][0] - ctx["at_start"][0]
    if made <= 0:
        return None
    return 1e3 * (ctx["stamps"][-1] - ctx["stamps"][0]) / made

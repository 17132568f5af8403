"""Real (unpadded) nodes of every graph whose micro-step finished inside the
window, over the window's wall seconds (first dispatch to the last step's
``block_until_ready``), over the chips of the cell."""


def read(ctx):
    w = ctx["window"]
    return w["nodes"] / w["wall_s"] / ctx["chips"]

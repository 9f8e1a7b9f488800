"""Device time of the fed_local_sgd kernel (the custom call
``%fed.local_sgd.N``) per round of the traced window: MCLR's budgeted
local SGD over every lane's slots."""


def read(ctx):
    seconds, calls = ctx.trace.kernel("fed.local_sgd")
    if not calls:
        return None
    return 1e3 * seconds / ctx.window["rounds"]

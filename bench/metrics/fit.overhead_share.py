"""Share of the training window outside the epochs' own time: 1 minus the
sum of ``fit`` history ``seconds`` (host clock around each epoch's
``run_epoch`` and ``block_until_ready``) over the window."""


def read(ctx):
    h = ctx.stash.get("history_s")
    if h is None or not ctx.window_s:
        return None
    return 100.0 * (1.0 - h / ctx.window_s)

"""Images whose logits came back inside the window, over the window."""


def read(ctx):
    return ctx.completed_in_window / ctx.seconds

"""Output tokens emitted in the window over its length, tokens/s."""


def read(ctx):
    return ctx.window.tokens / ctx.window.seconds

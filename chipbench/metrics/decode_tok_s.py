"""Output tokens stamped from the window's open to the first stamp at or
after its close, over that time (see ``Recorder.decode_tok_s``)."""


def read(ctx):
    return ctx.rec.decode_tok_s(ctx.seconds)

"""Tokens of the work done inside the window over the window's seconds: all
the work and all the time. Serving: prompt tokens of the prefills that
ended in the window plus the tokens generated in it (stats.processed_tokens);
training: the tokens of the window's steps over the time they took."""


def read(ctx):
    tokens = ctx["runner"].tokens_completed()
    return tokens / ctx["window_s"] if tokens > 0 else None

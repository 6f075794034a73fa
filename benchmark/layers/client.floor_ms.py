"""Load generator: median of 200 `GET /version` through the window's own
connection code, in ms. What a request costs with no query in it."""


def read(ctx):
    return ctx.floor_ms

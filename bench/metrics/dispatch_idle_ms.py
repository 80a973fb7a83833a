"""Device idle time per epoch under the trainer's ``dispatch`` span (the
jitted step call until it returns, before the device has its work), mean
over the cell's chips."""

from bench.scopes import idle_under_ms


def read(rec):
    return idle_under_ms(rec, lambda name: name == "dispatch")

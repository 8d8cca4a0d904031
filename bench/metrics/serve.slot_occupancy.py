"""Mean of the program's on-device gauge ``serve/slot_occupancy`` over the
window's decode steps."""


def read(inp):
    return inp.window["occupancy"]

"""Host clock around sample generation, the program's graph construction and
partition, dataset open (node reordering) and loader construction; for a
scanned cell also staging the set on the device."""


def read(ctx):
    return ctx["setup"]["data_prep_s"]

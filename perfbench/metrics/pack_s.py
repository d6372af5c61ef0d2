"""Host build: seconds the program spends packing the CSR
(``packsell.from_csr``), from its own host span ``packsell.pack``."""
from perfbench import recorder


def read(ctx):
    h = recorder.span("packsell.pack")
    return None if h is None else h["sum"]

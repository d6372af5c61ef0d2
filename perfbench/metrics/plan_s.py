"""Host build: seconds the program spends building the SpMV plan
(``plan.build_plan``), from its own host span ``packsell.plan_build``."""
from perfbench import recorder


def read(ctx):
    h = recorder.span("packsell.plan_build")
    return None if h is None else h["sum"]

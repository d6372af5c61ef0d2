"""Host build: seconds of ``packsell.from_csr`` plus ``plan.get_plan``,
on the host clock around the two calls (``drivers.build_operator``)."""


def read(ctx):
    return ctx["build_s"]

"""Set-up time: from the start of the process to the end of the job's
set-up (imports, graph build on the device, compiles or compile-cache
loads, warm-up), on the host clock."""


def read(ctx):
    return ctx["setup_s"]

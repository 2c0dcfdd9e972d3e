"""Device milliseconds per call of one compiled program, from the trace's
``XLA Modules`` line: ``args["module"]`` is the program's name as XLA has
it (``jit_<function>``).  Nothing to read (the program is not on the path
any more) gives nothing."""


def read(ctx, args):
    red = ctx.trace_reduced
    if not red:
        return None
    calls = red["module_calls"].get(args["module"], 0)
    if not calls:
        return None
    return 1000.0 * red["module_seconds"][args["module"]] / calls

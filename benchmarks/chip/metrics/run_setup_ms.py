"""The traced job's in-run set-up: the program's ``repro.run.setup`` span
(``stats["run_setup_s"]``), from the entry of ``repro.core.run`` to its
first window request (initial model and client state, the evaluator's
upload of every test split, the compiled-program lookup), which every job
pays.  None for a program without the span."""


def read(ctx):
    setup_s = ctx.stats.get("run_setup_s")
    if setup_s is None:
        return None
    return setup_s * 1e3

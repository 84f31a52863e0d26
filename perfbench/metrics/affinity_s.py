"""Mean seconds per window job of the program's ``fit.affinity`` span
(it ends in ``block_until_ready`` on the operator's scales)."""
from perfbench import program_spans


def read(ctx):
    return program_spans.mean(secs.get("fit.affinity")
                              for _, secs in program_spans.window_jobs(ctx))

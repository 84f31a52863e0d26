"""Mean programs per window job that JAX compiled or loaded from the
persistent cache: the ``job`` span's ``jit_programs``."""
from perfbench import program_spans


def read(ctx):
    return program_spans.mean(job.attrs.get("jit_programs", 0)
                              for job, _ in program_spans.window_jobs(ctx))

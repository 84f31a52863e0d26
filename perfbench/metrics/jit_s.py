"""Mean seconds per window job that JAX spent tracing, lowering and
compiling or loading programs: the ``job`` span's ``jit_s``."""
from perfbench import program_spans


def read(ctx):
    return program_spans.mean(job.attrs.get("jit_s", 0.0)
                              for job, _ in program_spans.window_jobs(ctx))

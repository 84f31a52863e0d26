"""Mean seconds per window job of the program's points-input spans:
``job.load`` + ``job.to_device`` (the last ends in
``block_until_ready``)."""
from perfbench import program_spans

PHASES = ("job.load", "job.to_device")


def read(ctx):
    return program_spans.mean(
        sum(secs[p] for p in PHASES) if all(p in secs for p in PHASES)
        else None for _, secs in program_spans.window_jobs(ctx))

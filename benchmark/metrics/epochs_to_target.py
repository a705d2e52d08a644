"""Epochs a job took to meet the cell's target: the exact count from the
program's per-epoch quality, mean over the window's finished jobs (every job
starts from the same model, so they agree). Layer: models."""


def read(ctx):
    jobs = ctx.window.jobs
    if not jobs:
        return None
    return sum(epochs for _, epochs in jobs) / len(jobs)

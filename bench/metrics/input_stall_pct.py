"""Share of the window the training loop waited for its next batch: the
input pipeline's own stall account (``DataPipeline.report.stall_s``)."""


def compute(rec):
    return 100.0 * rec["stall_s"] / rec["window_s"]

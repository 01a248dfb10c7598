"""Host seconds per window step fetching the step's outputs once they
are ready (``runtime/program.py:fetch_rows``)."""


def read(run):
    return run.step_spans.get("fetch", 0.0) / run.steps \
        if run.steps else None

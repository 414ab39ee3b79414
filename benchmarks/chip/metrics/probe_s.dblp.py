"""Seconds of the stream service's admission probe (``service.probe``
spans: the Gershgorin bound and the SLQ Lanczos probe, up to the fetch
of its estimate) in the run, from the program's own spans."""
import program_spans


def read(ctx):
    return program_spans.total_s("service.probe")

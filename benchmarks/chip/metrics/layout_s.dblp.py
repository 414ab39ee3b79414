"""Seconds the stream service spent building node-blocked layouts
(``service.layout`` spans, opened only when a layout is built) in the
run, from the program's own spans."""
import program_spans


def read(ctx):
    return program_spans.total_s("service.layout")

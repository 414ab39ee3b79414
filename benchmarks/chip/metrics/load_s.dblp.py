"""Seconds of program loading (trace, lower, backend compile or
persistent-cache retrieval) inside the program's spans in the run:
the compile seconds the program's span recorder attributed to them."""
import program_spans


def read(ctx):
    return program_spans.compile_s()

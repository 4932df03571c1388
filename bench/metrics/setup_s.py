"""setup_s: host seconds from the process's start to the window's first
request (imports, device start-up, corpus, engine, warm-up and, on a first
run, compiling), less the index step: building the index or loading it
from the index cache, whichever the run did, never shows here."""


def read(run):
    return run.setup_s

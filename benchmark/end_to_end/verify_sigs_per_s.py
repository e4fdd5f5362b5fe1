"""verify_sigs_per_s: peers' partial signatures verified per second, over
every set submitted in the window, from the first submission to the last
completion."""


def read(run):
    return run.window.rate()

"""aggregate_sigs_per_s: validators' group signatures aggregated from their
threshold partials and verified per second, over every slot submitted in
the window, from the first submission to the last completion."""


def read(run):
    return run.window.rate()

"""setup_s: seconds from process start to the first timed submission
(keys, inputs, pubkey pinning, and the warm requests that trace, load or
compile every program the window runs)."""


def read(run):
    return run.setup_s

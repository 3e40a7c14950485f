"""Set-up: seconds from the process's start to the window's start
(loading, making the weights, compiling or loading compiled programs,
warming up, and a training cell's checked steps)."""


def read(run, ctx):
    return run["setup_s"]

"""New files in the compile cache between the window's edges: programs
compiled while the clock ran (should be 0)."""


def read(run):
    return float(run.cache_files_close - run.cache_files_open)

"""Traffic drivers, one module per ``driver`` name of a workload file.

Each has ``run(ctx) -> common.Window``: it builds the program's controller,
drives the compared first steps, runs the window between
``ctx.open_window()`` and ``ctx.close_window(out)``, reads the device's
peak memory and frees the program's state before it returns.
"""

"""Live rows per launch in the window, from the server's own dispatch
telemetry (a count of rows over a count of launches)."""


def read(ctx):
    t = ctx["telemetry"]
    if not t["dispatches"]:
        return None
    return t["live_rows"] / t["dispatches"]

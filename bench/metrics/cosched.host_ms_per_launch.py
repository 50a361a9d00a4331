"""Host milliseconds per launch in the co-scheduler: time inside the
window's ``launch_mixed`` and ``gather`` calls (blocking waits on the
device included) over the launches they made."""


def read(ctx):
    probes = ctx["probes"]
    if not probes.launches:
        return None
    return (probes.launch_s + probes.gather_s) / len(probes.launches) * 1e3

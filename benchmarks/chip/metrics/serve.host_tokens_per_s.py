"""Output tokens of the host-placed engines per second over the traced
part of the window, host clock (tiered cells only)."""


def read(run):
    traced = run.data.get("traced")
    if not run.data.get("host_engines") or not traced:
        return None
    return traced["host_tokens"] / traced["seconds"]

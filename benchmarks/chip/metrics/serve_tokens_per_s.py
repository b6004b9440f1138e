"""Output tokens all engines produced in the measured window (first tokens
from prefill included), over the window's host-clock seconds."""


def read(run):
    return run.data["tokens"] / run.data["window_s"]

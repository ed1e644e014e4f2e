"""Request tokens of the window's requests that completed ok, over the
window's seconds."""


def read(run):
    v = run.values
    return v["completed_ok"] * v["request_tokens"] / run.seconds

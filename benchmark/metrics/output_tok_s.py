"""Output tokens delivered between the window's two instants, over
its length: the tap's count read at both marks, with the clock."""


def read(run):
    a, b = run["opened"], run["closed"]
    if b["t"] <= a["t"]:
        return None
    return (b["tokens"] - a["tokens"]) / (b["t"] - a["t"])

"""How late the generator ran: send instant minus due instant."""
from benchmark.harness.readers import tail_of


def _late(r):
    if r.get("t_sent") is None:
        return None
    return (r["t_sent"] - r["due"]) * 1e3


read = tail_of(_late, 99, failed_sort_last=False)

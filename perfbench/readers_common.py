"""What more than one reader needs."""

import re


def matching(table: dict, patterns) -> dict:
    """The rows of a trace table whose names match any pattern."""
    rx = [re.compile(p) for p in patterns]
    return {n: v for n, v in table.items() if any(r.search(n) for r in rx)}


def prom(text: str, name: str, **labels) -> float:
    """Sum of a Prometheus series over the label sets that match."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name) or line[len(name):len(name) + 1] not in ("{", " "):
            continue
        head, _, value = line.rpartition(" ")
        if all(f'{k}="{v}"' in head for k, v in labels.items()):
            try:
                total += float(value)
            except ValueError:
                pass
    return total

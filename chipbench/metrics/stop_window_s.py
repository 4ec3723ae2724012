"""Mean stopped time of the window's moves: from the source's last step
returning to the first step on the restored state returning."""


def value(run, record):
    moves = record.get("moves") or []
    if not moves:
        return None
    return sum(resumed - stopped for stopped, resumed, *_ in moves) / len(moves)

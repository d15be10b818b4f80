"""Helpers of the readers: a dotted path into what the driver observed."""


def dig(obs, path):
    cur = obs
    for part in path.split("."):
        if cur is None:
            return None
        cur = cur.get(part) if isinstance(cur, dict) else getattr(cur, part, None)
    return cur

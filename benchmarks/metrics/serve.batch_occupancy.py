"""Mean requests per wheel over the window (the stamp's ``stack``, one
value per distinct wheel). Moves ``req_per_s``."""


def read(obs):
    w = obs.get("wheels")
    return sum(x["stack"] for x in w) / len(w) if w else None

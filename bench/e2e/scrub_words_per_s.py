"""Words swept (scanned; decoded and written back where flagged) over the
whole window, damage placement between sweeps included."""


def compute(window: dict) -> float | None:
    if window.get("kind") != "sweeps":
        return None
    return window["words_swept"] / (window["t1"] - window["t0"])

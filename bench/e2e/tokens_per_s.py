"""Output tokens served over the whole window: every token the engine
generated (one a busy slot a step, and the first token of each admission
from its prefill), admissions and retirements inside the window included,
over the window from its open to the end of its last step."""


def compute(window: dict) -> float | None:
    if window.get("kind") != "serving":
        return None
    return window["tokens"] / (window["t1"] - window["t0"])

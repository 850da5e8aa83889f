"""The toy family's plain reference: imports nothing of the program."""


def step_scores(f: dict, prompt: list, steps: int) -> list:
    return [((sum(prompt) * 31 + step * 17) % 101) / 101.0 / f["block"]
            for step in range(steps)]

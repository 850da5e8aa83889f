"""``kind: toy``: a cell of another kind. Its "program" answers each
request with blocks of tokens and what each denoising step scored; the
configuration's family decides ``correct`` over those scores."""

from . import cells, common, readers

CELL_KEYS = {"steps_per_block"}
PEAK = {"bf16_flops_per_s": 1e9}


def program(cell: dict, fields: dict, seed: int) -> list:
    """The system under test."""
    traffic = cell["traffic_data"]
    steps = int(cell["steps_per_block"]) * int(traffic["blocks"])
    finished = []
    for index in range(int(traffic["requests"])):
        prompt = [(seed + index + i) % fields["vocab_size"]
                  for i in range(int(traffic["prompt_tokens"]))]
        total = 31 * sum(prompt)
        finished.append({
            "index": index, "prompt": prompt,
            "tokens": [1] * (fields["block"] * int(traffic["blocks"])),
            "scores": [((total + 17 * s) % 101) / 101.0 / fields["block"]
                       for s in range(steps)]})
    return finished


def run(cell: dict, layer_metrics: list, args, device: dict,
        process_start: float) -> str:
    family = cells.family_of(cell["config_data"])
    fields = family.fields(cell["config_data"])
    finished = program(cell, fields, args.seed)
    metrics = readers.read_all(layer_metrics, {
        "cell": cell, "fields": fields, "costs": family.costs,
        "chips": cell["chips"], "peak": PEAK, "window_s": args.seconds,
        "finished": finished})
    compared = family.step_check(cell, fields, finished)
    return common.result_line(
        correct=all(entry["ok"] for entry in compared.values()),
        attempted=len(finished), failed=0, metrics=metrics, device=device,
        compared=compared)

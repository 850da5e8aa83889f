"""The toy family's required operations: a token meets the experts it is
routed to, not all of them."""


def serve_request_flops(f: dict, prompt_tokens: int, output_tokens: int) -> int:
    active = 3 * f["embed_dim"] * f["expert_dim"] * f["experts_per_token"]
    return 2 * f["n_layers"] * active * (prompt_tokens + output_tokens)

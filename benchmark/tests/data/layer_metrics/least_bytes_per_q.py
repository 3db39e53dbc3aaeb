"""least_bytes_per_q: source program_counter; moves: qps. The bytes the
traced requests needed, by the kinds module's count, a request."""


def read(obs):
    n = obs["requests_in_trace"]
    return obs["least_bytes_in_trace"] / n if n else None

"""Prompt tokens served from the prefix cache / prompt tokens of the
counted requests, in percent: the server's counter delta over the
client's own count of what it sent."""


def read(ctx, counter="tpu_inf_tokens_prefix_cached_total"):
    a, b = ctx["metrics_open"], ctx["metrics_end"]
    sent = sum(r["prompt_tokens"] for r in ctx["ok"])
    if counter not in b or not sent:
        return None
    return 100.0 * (b[counter] - a.get(counter, 0.0)) / sent

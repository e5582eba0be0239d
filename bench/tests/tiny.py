"""Shrinks a cell to a size the CPU runs in seconds (widths of
``ModelConfig.reduced()``), for the benchmark's own tests."""

TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
              "vocab_size": 512}


def adjust(cfg_json, traffic):
    cfg_json.update(TINY_MODEL)
    cfg_json["serve"].update(max_seq=256, num_slots=4, num_pages=64)
    traffic.update(warm_s=0.5, max_total=256)
    traffic["prompt"].update(min=16, max=200)
    traffic["output"].update(min=4, max=40)
    if "median" in traffic["prompt"]:
        traffic["prompt"]["median"] = 48
        traffic["output"]["median"] = 8
    if "clients" in traffic:
        traffic.update(clients=4, pool=400)
    if "rate_per_s" in traffic:
        traffic["rate_per_s"] = 12.0
    traffic["check"] = {"min_tokens": 40, "max_requests": 4}

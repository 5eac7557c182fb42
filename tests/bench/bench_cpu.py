"""A CPU-sized configuration and mixes for the benchmark's tests: the
granite-3-8b-smoke widths of the program's registry."""
import copy

MODEL = {"name": "smoke", "arch": "granite-3-8b-smoke", "hidden_size": 256,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
         "vocab_size": 1024, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": True}

_SERVING = {"slots": 4, "prefill_bucket": 64,
            "freekv": {"page_size": 8, "budget": 64, "n_sink": 8,
                       "n_window": 8, "tau": 0.8, "sync_interval": 4}}

CLOSED = {"loop": "closed", "clients": 3, "prompt_tokens": [150, 300],
          "output_tokens": [40, 80], "block": 4, "requests": 12,
          "serving": _SERVING, "sample_requests": 3, "trace_seconds": 0}

OPEN = {"loop": "open", "rate_per_s": 6.0, "prompt_tokens": [150, 300],
        "output_tokens": [20, 40], "block": 4, "serving": _SERVING,
        "sample_requests": 3, "trace_seconds": 0}


def mix(kind):
    return copy.deepcopy(CLOSED if kind == "closed" else OPEN)

"""Operations and bytes of the RWKV-6 (Finch) LM's serving work, computed
from the configuration's published widths alone, and the least time the
chip could take for them (peaks from ``harness/peaks.py``).

- A decode step of ``rows`` rows reads every block matrix and the head
  once (the embedding is a gather of ``rows`` rows, not counted), and reads
  and writes each row's recurrent state (float32 WKV state, two shift
  vectors); it does 2 operations a matrix weight a row. Its least time is
  the larger of the bytes at the HBM bandwidth and the operations at the
  bf16 peak.
- A prefill of ``tokens`` prompt tokens over ``prompts`` prompts does 2
  operations a block matrix weight a token and the head once a prompt (the
  first token's logits), at the bf16 peak.

The WKV recurrence itself (about 4 H hd^2 operations a token a layer, under
0.5 % of the matrices' at the published widths) is left out.
"""
from __future__ import annotations


def rwkv6_widths(config: dict) -> dict:
    """The widths the counts need, under the configuration's keys."""
    return {"layers": config["num_hidden_layers"], "d": config["hidden_size"],
            "head": config["head_size"], "ffn": config["intermediate_size"],
            "vocab": config["vocab_size"], "mix": config["time_mix_extra_dim"],
            "decay": config["time_decay_extra_dim"]}


def rwkv6_block_weights(config: dict) -> dict:
    """Weights of one block: ``matrix`` (bf16) and ``vector`` (float32)."""
    w = rwkv6_widths(config)
    d, f, m, r = w["d"], w["ffn"], w["mix"], w["decay"]
    matrix = (5 * d * d            # receptance, key, value, gate, output
              + d * 5 * m + 5 * m * d      # token-shift LoRA
              + d * r + r * d              # decay LoRA
              + d * f + f * d + d * d)     # channel mix: key, value, receptance
    vector = (d + 5 * d + d + d + 2 * d    # maa_x, maa_wkvrg, decay, bonus, ln_x
              + 2 * d + 2 * d + 2 * d)     # ffn maa_k/r, ln1, ln2
    return {"matrix": matrix, "vector": vector}


def rwkv6_decode(config: dict, rows: int) -> dict:
    """One decode step of ``rows`` rows: bf16 operations and HBM bytes."""
    w = rwkv6_widths(config)
    blk = rwkv6_block_weights(config)
    head = w["vocab"] * w["d"]
    matrix = w["layers"] * blk["matrix"] + head
    vector = w["layers"] * blk["vector"] + 4 * w["d"]      # + ln0, ln_out
    state = w["layers"] * (w["d"] // w["head"] * w["head"] ** 2 * 4
                           + 2 * w["d"] * 2)               # per row
    return {"flops": 2.0 * matrix * rows,
            "hbm_bytes": 2.0 * matrix + 4.0 * vector + 2.0 * state * rows}


def rwkv6_prefill(config: dict, tokens: int, prompts: int) -> dict:
    """A prefill of ``tokens`` prompt tokens over ``prompts`` prompts."""
    w = rwkv6_widths(config)
    blocks = w["layers"] * rwkv6_block_weights(config)["matrix"]
    return {"flops": 2.0 * (blocks * tokens + w["vocab"] * w["d"] * prompts)}


def least_time(peaks: dict, *, flops: float, hbm_bytes: float = 0.0) -> float:
    """The larger of the operations at the bf16 peak and the bytes at the
    HBM bandwidth, in seconds."""
    return max(flops / peaks["bf16_flops"], hbm_bytes / peaks["hbm_bw"])


def lm_least_time(peaks: dict, config: dict, *, decode_steps: int, rows: int,
                  prefill_tokens: int, prefill_prompts: int) -> float:
    """Least time of a window's LM work: its decode steps and prefills."""
    return (decode_steps * least_time(peaks, **rwkv6_decode(config, rows))
            + least_time(peaks, **rwkv6_prefill(config, prefill_tokens,
                                                prefill_prompts)))

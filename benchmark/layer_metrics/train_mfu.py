"""Layer: trainer (workload/train.py, parallel/). Model FLOP/s
utilisation: this run's train_tokens_per_s times the FLOPs the forward
and backward passes need per token (matmuls x6, attention causal and
windowed, recomputation NOT counted: benchmark/harness/counts.py) over
the chips' bf16 peak (benchmark/harness/peaks.py). Source: host clock
(the progress file's per-step stamps) over a computed count."""
from benchmark.harness import counts, peaks


def read(run):
    rate = run["e2e"].get("train_tokens_per_s")
    if not rate:
        return None
    traffic = run["traffic"]
    per_token = counts.train_flops_per_token(
        run["config"], int(traffic["seq_len"]), int(run["config"]["sliding_window"]))
    chips = int(run["config"].get("chips", 1))
    return 100.0 * rate * per_token / (chips * peaks.peak(run["device_kind"], "bf16_flops"))

"""ssd_roofline: the least time the chip could take for the Mamba-2
recurrence of the prefill flushes and decode iterations of the traced
span, over the device time under the `zamba2.ssd` scope there, in %.

Counted from the configuration's model block, never from the program,
for every layer: 5 x heads x head size x state FLOPs per token (decay,
update, read-out); bytes: each row's f32 state written once per call
(and, for a decode step, read once), and each token's x, B, C and dt read
once in bf16. The least time is the larger of FLOPs over peak FLOP/s and
bytes over peak bandwidth, per flush or iteration. Padding to a length
bucket costs time and earns nothing."""
from bench import scopes

BF16, F32 = 2, 4


def work(m, tokens: int, rows: int, state_in: bool):
    """(FLOPs, bytes) of the recurrence over `tokens` tokens in `rows`
    rows, every layer."""
    d, n, g = m["hidden_size"], m["mamba_d_state"], m["mamba_ngroups"]
    di = m["mamba_expand"] * d
    hd = m["mamba_headdim"]
    nh = di // hd
    layers = m["num_hidden_layers"]
    flops = layers * tokens * 5 * nh * hd * n
    state = layers * rows * nh * hd * n * F32 * (2 if state_in else 1)
    inputs = layers * tokens * (di + 2 * g * n + nh) * BF16
    return flops, state + inputs


def read(run):
    sc = scopes.of(run)
    if sc is None or not sc.seconds.get("zamba2.ssd"):
        return None
    a, b = run.traced["mono"]
    m = run.cell.config["model"]
    peak, bw = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    parts = [work(m, sum(prompts), len(prompts), False)
             for _calls, prompts in run.obs.prefill_flushes(a, b)]
    parts += [work(m, rows, rows, True)
              for rows, _ctx in run.obs.decode_iterations(a, b)]
    if not parts:
        return None
    least = sum(max(f / peak, byts / bw) for f, byts in parts)
    return 100.0 * least / sc.seconds["zamba2.ssd"]

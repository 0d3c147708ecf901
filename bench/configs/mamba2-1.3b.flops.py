"""Model FLOPs of one mamba2 train step, from the published shapes.

Forward FLOPs per token (a multiply-add counts 2), per layer:
  projections  2 d (2 d_inner + 2 G N + H)     z, x, B/C and dt
  conv         2 K (d_inner + 2 G N)           depthwise, K taps
  SSD          2 Q G N                          C B^T inside a chunk
               2 Q H P                          (C B^T * L) x inside a chunk
               2 H P N + 2 H P N                chunk states and their output
  out proj     2 d_inner d
and once per token the tied head, 2 d V. The chunk terms count the whole
Q x Q block, as the SSD algorithm computes it. A train step is 3 x the
forward (backward = 2 x forward); recomputation under remat is not
counted. Norms, gates and the loss's softmax are left out.
"""


def forward_flops_per_token(c: dict) -> float:
    d, L, V = c["d_model"], c["n_layer"], c["vocab_size"]
    N, K, G = c["d_state"], c["d_conv"], c["ngroups"]
    di = c["expand"] * d
    P = c["headdim"]
    H = di // P
    Q = c["chunk_size"]
    per_layer = (2 * d * (2 * di + 2 * G * N + H)
                 + 2 * K * (di + 2 * G * N)
                 + 2 * Q * G * N + 2 * Q * H * P + 4 * H * P * N
                 + 2 * di * d)
    return L * per_layer + 2 * d * V


def flops_per_step(config: dict, traffic: dict) -> float:
    tokens = traffic["batch"] * traffic["seq"]
    return 3.0 * forward_flops_per_token(config) * tokens

"""The comparisons that decide ``correct``.

Serving: tokens the engine generated greedily are teacher-forced through the
plain reference. With random weights the largest logit changes on rounding,
so the rule is the chip smoke's (PERF.md, PR 21): no served token sits more
than 8 bf16 steps (at the maximum's magnitude) under the reference's maximum,
and most are its exact argmax. Two correct bf16 programs differ by ~3 such
steps; a wrong program misses by ~100 and matches the argmax almost never.
The smoke asked for 90% exact on 160 tokens and saw 94-98%; here a run checks
64 tokens and the first chip runs of PR 24 read 57-63 of 64 (89-98%), so a
90% line would fail one correct run in ten by chance. The line is 75%: five
standard deviations under what a correct program reads, far above a wrong
one. The 8-step rule, which is the one that discriminates, is unchanged.

Training: the trainer's loss at the initial parameters against the
reference's float32 loss on the same sequences. Both are means over thousands
of tokens of a loss near ln(vocab) = 11.4; bf16 matmuls move single logits by
~1e-2 and the mean by far less, so 2e-3 relative catches a wrong mask, a wrong
shift, a missing layer or RoPE variant (each moves it by > 1e-2) and still
passes bf16 against float32.
"""

from __future__ import annotations

import math

TIE_BF16_STEPS = 8.0
MIN_EXACT = 0.75
LOSS_RTOL = 2e-3


def greedy_agreement(logits, pairs) -> dict:
    """``logits``: reference logits [n, S, V] (numpy) of prompt+generated;
    ``pairs``: ``[(prompt_len, generated_tokens), ...]``."""
    checked = exact = 0
    worst = 0.0
    for i, (plen, gen) in enumerate(pairs):
        for j, tok in enumerate(gen):
            row = logits[i, plen + j - 1]
            top = float(row.max())
            step = 2.0 ** (math.floor(math.log2(max(abs(top), 1e-30))) - 7)
            under = (top - float(row[tok])) / step
            checked += 1
            exact += under <= 0
            worst = max(worst, under)
    ok = (checked > 0 and exact >= MIN_EXACT * checked
          and worst <= TIE_BF16_STEPS and math.isfinite(worst))
    return {"ok": bool(ok), "tokens_checked": checked,
            "exact_argmax": int(exact),
            "worst_bf16_steps_under_max": worst}


def loss_agreement(program_loss: float, reference_loss: float) -> dict:
    rel = abs(program_loss - reference_loss) / max(abs(reference_loss), 1e-9)
    ok = math.isfinite(program_loss) and rel <= LOSS_RTOL
    return {"ok": bool(ok), "program": program_loss,
            "reference": reference_loss, "rel_diff": rel}

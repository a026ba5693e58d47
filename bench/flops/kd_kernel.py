"""Required work of the fused head + loss kernel ``_kd_kernel``: the
forward of the student's head over the vocabulary with its softmax, and,
when distilling, the teacher's head and softmax and the KL terms.  The
kernel runs forward only (the backward is a separate XLA scan), so the
backward is not its work.  Recomputation does not count."""

SOFTMAX_FLOPS = 5   # max, subtract, exp, sum, scale per logit


def required(*, tokens: int, d_student: int, vocab: int,
             d_teacher: int = 0, itemsize: int = 2):
    """(flops, bytes) of one forward over ``tokens`` rows."""
    heads = d_student + d_teacher
    flops = 2 * tokens * heads * vocab
    flops += SOFTMAX_FLOPS * tokens * vocab * (2 if d_teacher else 1)
    nbytes = (heads * vocab + tokens * heads) * itemsize + 3 * tokens * 4
    return flops, nbytes

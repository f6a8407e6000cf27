"""The mean pass at which a token exits, ``sum_t t p_t`` over the mean
exit distribution the program's exit gate keeps in the step's ``aux``
(``exit.pdf``, the last training call's) as the family last read it,
after the checked updates.  1 is every token at the first exit, T at
the last.  **An indicator for the serve side, not a lever on training**:
in training all T passes always run, so this number cannot move
``train_mfu``, the metric its entry has to name (BENCHMARK.json has no
serving metric to give it); a served model's depth a token would follow
it.  It is read after the check's 3 updates and not from the timed
window: the driver frees the step before a reader runs.  None for a family
that keeps no such distribution."""


def read(obs):
    pdf = getattr(obs["ctx"]["family"], "last_pdf", None)
    if not pdf:
        return None
    return float(sum(t * p for t, p in enumerate(pdf, start=1)))

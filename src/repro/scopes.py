"""The names the program gives its work in a profiler trace.

Device scopes are ``jax.named_scope`` names.  They reach every compiled op's
``op_name`` metadata through ``lax.scan``, ``jax.checkpoint`` and autodiff:
a phase's backward ops read ``transpose(jvp(<scope>))``.  An op belongs to
the innermost of the four phase scopes in its ``op_name``; ``ATTENTION`` is a
tag that cuts across the phases.

Host spans are ``jax.profiler`` annotations on the trace's own clock.  They
record only while a profiler session runs (``jax.profiler.trace``), and cost
about a microsecond each otherwise.
"""

# device scopes: the phases of a step or round
TRUNK = "ringada.trunk"            # embedding and the frozen blocks' forward
HOT = "ringada.hot"                # hot blocks, forward and backward
HEAD = "ringada.head"              # head projection and loss
OPTIMIZER = "ringada.optimizer"    # AdamW, gradient norm, trainable write-back
PHASES = (TRUNK, HOT, HEAD, OPTIMIZER)
# device tag: the attention core, inside whichever phase runs it
ATTENTION = "ringada.attention"

# host spans of RingSession.step (ROUND holds DATA, then DISPATCH) and of the
# host sync in RoundMetrics
ROUND = "ringada.round"
DATA = "ringada.data"
DISPATCH = "ringada.dispatch"
SYNC = "ringada.sync"

"""Mean duration of one decode step of the checked full-width member:
its `serve.decode_step.<member>` markers in the traced stretch
(FleetModel.generate; the decode, and its token on the host), in ms.
None where the program leaves no such markers in the trace."""


def read(ctx):
    name = "serve.decode_step." + ctx["cfg"]["fleet"]["checked"]
    durs = [e - s for s, e, n in ctx["trace"].spans if n == name]
    return 1e-6 * sum(durs) / len(durs) if durs else None

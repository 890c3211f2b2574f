"""Share of the POA-eligible windows (three or more layers) whose
consensus the device computed: ``poa_device_windows`` over
``poa_eligible_windows``, summed over the traced contigs."""


def read(ctx):
    reg = ctx["registry"]
    eligible = reg.get("poa_eligible_windows")
    if not eligible:
        return None
    return reg.get("poa_device_windows", 0) / eligible

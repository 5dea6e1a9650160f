"""Device-idle time inside the harness's ``run_campaign`` span, per sweep:
the chip waiting before the first chunk program and between chunk programs
while ``core/campaign.py`` works on the host (mean over the cell's chips)."""


def read(r):
    idle, n = r.idle_in("run_campaign")
    if n == 0 or r.busy_s <= 0:
        return None
    return 1e3 * idle / n

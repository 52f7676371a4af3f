"""Seeded cache-branding violations: branding kwargs silently dropped."""


def leaks(self, session, batch, plan, child, condition):
    from hyperspace_tpu.exec.device import device_filter_mask, stage_filter_columns

    mask = self._filter_mask(plan, child)  # drops kept
    m2 = device_filter_mask(session, batch, condition)  # drops scan_key
    stage_filter_columns(session, batch, condition)  # drops scan_key
    return mask, m2

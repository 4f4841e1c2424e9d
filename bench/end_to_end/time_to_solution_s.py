"""Seconds per campaign: the window, from its start to the completion of
the campaign in flight when it closed, over the campaigns completed. Each
campaign runs every round to its final estimate, whose accuracy the check
holds."""


def read(window):
    if not window.campaigns:
        return None
    return window.seconds / window.campaigns

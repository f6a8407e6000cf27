"""End of the first ``train.call`` to the window's open: the first update's
wait (the device catching up on the placed state), two more updates
with their fetches, the check's gradient-norm and change-norm programs.
One of the six pieces ``setup_timeline`` cuts ``setup_s`` into."""
import setup_timeline


def read(obs):
    return setup_timeline.piece(obs, "checked_updates_s.setup")
